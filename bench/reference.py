"""The plain float32 reference's shared parts, and the weights it and the
program both use.

Written from the model's equations (Opto-ViT, arXiv:2507.07044: MGNet
region scores, top-k patch selection, a ViT encoder; ViT,
arXiv:2010.11929), with none of the program's code: no backend, kernels,
quantization, mask cache or batching. Every matmul runs at float32
``highest`` precision, one frame's computation never sees another's.

What differs between models lives in the model module a configuration
names (``bench/models/<model>.py``, see ``bench/models/__init__.py``):
its parameter tree (``init_params``) and its forward pass
(``classify``). What every model served behind the RoI gate shares lives
here: the MGNet score pass and its weights, the gate's top-k rule, the
layers' plain equations (``ln``, ``gelu``, ``patches``, ``attention``),
the seeding (``make_init``), the blocked evaluation (``ReferenceModel``)
and the comparison (``served_gaps``).

``make_init`` makes the random weights from the seed, on the device, in
the parameter layout the program takes (``StreamServer(params=...)``).
The program quantizes them itself; the reference reads the same float32
arrays.

The temporal mask-cache rule is restated here as the paper's deployment
describes it: a frame reuses the region scores of the last scored frame
of its stream unless ``refresh`` frames have passed since that one was
scored or the mean absolute pixel difference to it exceeds
``threshold``.

Departure from the published ViT, also the program's stated equation:
GELU is the tanh form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["dense", "near", "init_mgnet", "make_init", "scoring_frames",
           "ln", "gelu", "patches", "attention", "mgnet_scores",
           "top_patches", "ReferenceModel", "served_gaps"]


# -- weights -----------------------------------------------------------------

def dense(key, fan_in, shape, gain=1.0):
    return jax.random.normal(key, shape, jnp.float32) * (
        gain / np.sqrt(fan_in))


def near(key, shape, center, spread):
    return center + spread * jax.random.normal(key, shape, jnp.float32)


def init_mgnet(nk, cfg: dict) -> dict:
    """MGNet's weights, each drawn with the next key ``nk()`` gives.
    Biases and LayerNorm gains and shifts are drawn too (not zeros and
    ones), so that the comparison covers them."""
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    md = cfg["mgnet_embed"]
    mff = int(md * cfg["mgnet_mlp_ratio"])
    return {
        "patch_embed": {"w": dense(nk(), p_in, (p_in, md)),
                        "b": near(nk(), (md,), 0.0, 0.02)},
        "cls_token": near(nk(), (1, 1, md), 0.0, 0.02),
        "pos_embed": near(nk(), (1, n + 1, md), 0.0, 0.02),
        "block": {
            "ln1": {"g": near(nk(), (md,), 1.0, 0.1),
                    "b": near(nk(), (md,), 0.0, 0.02)},
            "wqkv": dense(nk(), md, (md, 3 * md)),
            "wo": dense(nk(), md, (md, md)),
            "ln2": {"g": near(nk(), (md,), 1.0, 0.1),
                    "b": near(nk(), (md,), 0.0, 0.02)},
            "w1": dense(nk(), md, (md, mff)),
            "b1": near(nk(), (mff,), 0.0, 0.02),
            "w2": dense(nk(), mff, (mff, md)),
            "b2": near(nk(), (md,), 0.0, 0.02),
        },
        "score": {"wq": dense(nk(), md, (md, md)),
                  "wk": dense(nk(), md, (md, md)),
                  "head_w": dense(nk(), n, (n, n)),
                  "head_b": near(nk(), (n,), 0.0, 0.02)},
    }


def make_init(model, cfg: dict, seed: int):
    """The whole weight tree of ``model`` (a model module) from ``seed``
    in one jitted call."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    key = jax.random.fold_in(jax.random.PRNGKey(int(state[0])),
                             int(state[1] >> 1))
    return jax.jit(lambda k: model.init_params(k, cfg))(key)


# -- the mask-cache rule -----------------------------------------------------

def scoring_frames(frames: np.ndarray, refresh: int,
                   threshold: float) -> np.ndarray:
    """For each frame of one stream (F, H, W, 3), in order, the index of
    the frame whose region scores it uses."""
    out = np.empty(len(frames), np.int64)
    ref = None
    for i, f in enumerate(frames):
        if ref is None or i - ref >= refresh:
            ref = i
        else:
            a = f[None].astype(np.float32)
            b = frames[ref].astype(np.float32)
            if float(np.abs(a - b).mean(axis=(1, 2, 3))[0]) > threshold:
                ref = i
        out[i] = ref
    return out


# -- the layers' equations ---------------------------------------------------

def ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def patches(images, p):
    b, h, w, c = images.shape
    x = images.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def attention(x, wq, wk, wv, heads):
    b, n, d = x.shape
    dh = d // heads

    def split(t):
        return t.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)

    q, k, v = split(x @ wq), split(x @ wk), split(x @ wv)
    a = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / np.sqrt(dh), axis=-1)
    return (a @ v).transpose(0, 2, 1, 3).reshape(b, n, d)


# -- the RoI gate ------------------------------------------------------------

def mgnet_scores(mp, images, cfg):
    """MGNet's region score of every patch of ``images``."""
    md = cfg["mgnet_embed"]
    eps = 1e-6                      # MGNet's own LayerNorm epsilon
    x = patches(images, cfg["patch"]) @ mp["patch_embed"]["w"] \
        + mp["patch_embed"]["b"]
    b = x.shape[0]
    cls = jnp.broadcast_to(mp["cls_token"], (b, 1, md))
    x = jnp.concatenate([cls, x], axis=1) + mp["pos_embed"]
    blk = mp["block"]
    wq, wk, wv = jnp.split(blk["wqkv"], 3, axis=-1)
    h = ln(x, blk["ln1"]["g"], blk["ln1"]["b"], eps)
    x = x + attention(h, wq, wk, wv, cfg["mgnet_heads"]) @ blk["wo"]
    h = ln(x, blk["ln2"]["g"], blk["ln2"]["b"], eps)
    x = x + gelu(h @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
    s = mp["score"]
    q_cls = x[:, :1] @ s["wq"]
    keys = x[:, 1:] @ s["wk"]
    s_cls = (q_cls @ keys.transpose(0, 2, 1))[:, 0] / np.sqrt(md)
    return s_cls @ s["head_w"] + s["head_b"]


def top_patches(scores, keep: int):
    """The ``keep`` patches a frame keeps, in order: highest scores first;
    among equal scores the lower patch index."""
    return jnp.argsort(-scores, axis=-1, stable=True)[:, :keep]


# -- evaluation --------------------------------------------------------------

class ReferenceModel:
    """Logits of chosen frames by ``model``'s ``classify`` (a model
    module), computed in blocks of ``block`` frames."""

    def __init__(self, model, params: dict, cfg: dict, keep: int,
                 block: int = 32):
        self.params, self.cfg, self.keep, self.block = params, cfg, keep, block
        self._fn = jax.jit(lambda p, x, s: model.classify(p, x, s, keep, cfg))

    def logits(self, images: np.ndarray, score_images: np.ndarray
               ) -> np.ndarray:
        out = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(images), self.block):
                x = images[i:i + self.block]
                s = score_images[i:i + self.block]
                pad = self.block - len(x)
                if pad:
                    x = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                                    x.dtype)])
                    s = np.concatenate([s, np.zeros((pad,) + s.shape[1:],
                                                    s.dtype)])
                out.append(np.asarray(self._fn(self.params, x, s))
                           [:self.block - pad])
        return np.concatenate(out)


def served_gaps(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per frame, how far the served class's reference logit lies below
    the reference's best, in units of that frame's reference logit
    standard deviation (random weights give near-flat logits, so the best
    class itself is often a near tie; the gap is not)."""
    ref = np.asarray(ref_logits, np.float64)
    got = ref[np.arange(len(ref)), np.asarray(served)]
    return (ref.max(-1) - got) / ref.std(-1)
