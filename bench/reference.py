"""Plain float32 reference of the served model, and the weights both use.

Written from the model's equations (Opto-ViT, arXiv:2507.07044: MGNet
region scores, top-k patch selection, a ViT encoder; ViT,
arXiv:2010.11929), with none of the program's code: no backend, kernels,
quantization, mask cache or batching. Every matmul runs at float32
``highest`` precision, one frame's computation never sees another's.

``init_params`` makes the random weights from the seed, on the device, in
the parameter layout the program takes (``StreamServer(params=...)``).
The program quantizes them itself; the reference reads the same float32
arrays.

The temporal mask-cache rule is restated here as the paper's deployment
describes it: a frame reuses the region scores of the last scored frame
of its stream unless ``refresh`` frames have passed since that one was
scored or the mean absolute pixel difference to it exceeds
``threshold``.

Departures from the published ViT, each also the program's stated
equation: GELU is the tanh form, LayerNorm's epsilon is the
configuration's ``norm_eps``, the classifier reads the final-normed
[cls] token with no bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_params", "make_init", "scoring_frames", "ReferenceModel",
           "served_gaps"]


def _dense(key, fan_in, shape, gain=1.0):
    return jax.random.normal(key, shape, jnp.float32) * (
        gain / np.sqrt(fan_in))


def _near(key, shape, center, spread):
    return center + spread * jax.random.normal(key, shape, jnp.float32)


def init_params(key, cfg: dict) -> dict:
    """Random float32 weights in the program's layout. Biases, LayerNorm
    gains and shifts are drawn too (not zeros and ones), so that the
    comparison covers them."""
    d, dff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    md = cfg["mgnet_embed"]
    mff = int(md * cfg["mgnet_mlp_ratio"])
    ks = iter(jax.random.split(key, 40))
    he = float(np.sqrt(2.0))

    def nk():
        return next(ks)

    blocks = {
        "ln1_g": _near(nk(), (L, d), 1.0, 0.1),
        "ln1_b": _near(nk(), (L, d), 0.0, 0.02),
        "attn": {name: _dense(nk(), d, (L, d, d), he)
                 for name in ("wq", "wk", "wv", "wo")},
        "ln2_g": _near(nk(), (L, d), 1.0, 0.1),
        "ln2_b": _near(nk(), (L, d), 0.0, 0.02),
        "ffn": {"w1": _dense(nk(), d, (L, d, dff), he),
                "b1": _near(nk(), (L, dff), 0.0, 0.02),
                "w2": _dense(nk(), dff, (L, dff, d), he),
                "b2": _near(nk(), (L, d), 0.0, 0.02)},
    }
    mgnet = {
        "patch_embed": {"w": _dense(nk(), p_in, (p_in, md)),
                        "b": _near(nk(), (md,), 0.0, 0.02)},
        "cls_token": _near(nk(), (1, 1, md), 0.0, 0.02),
        "pos_embed": _near(nk(), (1, n + 1, md), 0.0, 0.02),
        "block": {
            "ln1": {"g": _near(nk(), (md,), 1.0, 0.1),
                    "b": _near(nk(), (md,), 0.0, 0.02)},
            "wqkv": _dense(nk(), md, (md, 3 * md)),
            "wo": _dense(nk(), md, (md, md)),
            "ln2": {"g": _near(nk(), (md,), 1.0, 0.1),
                    "b": _near(nk(), (md,), 0.0, 0.02)},
            "w1": _dense(nk(), md, (md, mff)),
            "b1": _near(nk(), (mff,), 0.0, 0.02),
            "w2": _dense(nk(), mff, (mff, md)),
            "b2": _near(nk(), (md,), 0.0, 0.02),
        },
        "score": {"wq": _dense(nk(), md, (md, md)),
                  "wk": _dense(nk(), md, (md, md)),
                  "head_w": _dense(nk(), n, (n, n)),
                  "head_b": _near(nk(), (n,), 0.0, 0.02)},
    }
    return {
        "patch_embed": {"w": _dense(nk(), p_in, (p_in, d), he),
                        "b": _near(nk(), (d,), 0.0, 0.02)},
        "cls": _near(nk(), (1, 1, d), 0.0, 0.02),
        "pos": _near(nk(), (1, n + 1, d), 0.0, 0.02),
        "blocks": blocks,
        "final_ln_g": _near(nk(), (d,), 1.0, 0.1),
        "final_ln_b": _near(nk(), (d,), 0.0, 0.02),
        "head": _dense(nk(), d, (d, cfg["n_classes"]), he),
        "mgnet": mgnet,
    }


def make_init(cfg: dict, seed: int):
    """The whole weight tree from ``seed`` in one jitted call."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    key = jax.random.fold_in(jax.random.PRNGKey(int(state[0])),
                             int(state[1] >> 1))
    return jax.jit(lambda k: init_params(k, cfg))(key)


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


# -- the forward pass --------------------------------------------------------

def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def _patches(images, p):
    b, h, w, c = images.shape
    x = images.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def _attention(x, wq, wk, wv, heads):
    b, n, d = x.shape
    dh = d // heads

    def split(t):
        return t.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)

    q, k, v = split(x @ wq), split(x @ wk), split(x @ wv)
    a = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / np.sqrt(dh), axis=-1)
    return (a @ v).transpose(0, 2, 1, 3).reshape(b, n, d)


def _mgnet_scores(mp, images, cfg):
    md = cfg["mgnet_embed"]
    eps = 1e-6                      # MGNet's own LayerNorm epsilon
    x = _patches(images, cfg["patch"]) @ mp["patch_embed"]["w"] \
        + mp["patch_embed"]["b"]
    b = x.shape[0]
    cls = jnp.broadcast_to(mp["cls_token"], (b, 1, md))
    x = jnp.concatenate([cls, x], axis=1) + mp["pos_embed"]
    blk = mp["block"]
    wq, wk, wv = jnp.split(blk["wqkv"], 3, axis=-1)
    h = _ln(x, blk["ln1"]["g"], blk["ln1"]["b"], eps)
    x = x + _attention(h, wq, wk, wv, cfg["mgnet_heads"]) @ blk["wo"]
    h = _ln(x, blk["ln2"]["g"], blk["ln2"]["b"], eps)
    x = x + _gelu(h @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
    s = mp["score"]
    q_cls = x[:, :1] @ s["wq"]
    keys = x[:, 1:] @ s["wk"]
    s_cls = (q_cls @ keys.transpose(0, 2, 1))[:, 0] / np.sqrt(md)
    return s_cls @ s["head_w"] + s["head_b"]


def _classify(p, images, score_images, keep, cfg):
    """Logits of ``images`` whose kept patches are chosen by the region
    scores of ``score_images`` (the frames whose scores they reuse)."""
    eps = cfg["norm_eps"]
    scores = _mgnet_scores(p["mgnet"], score_images, cfg)
    # highest scores first; among equal scores the lower patch index
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :keep]
    x = _patches(images, cfg["patch"]) @ p["patch_embed"]["w"] \
        + p["patch_embed"]["b"] + p["pos"][:, 1:]
    x = jnp.take_along_axis(x, order[:, :, None], axis=1)
    b, _, d = x.shape
    cls = jnp.broadcast_to(p["cls"] + p["pos"][:, :1], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1)

    def layer(x, lp):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        a = lp["attn"]
        x = x + _attention(h, a["wq"], a["wk"], a["wv"],
                           cfg["n_heads"]) @ a["wo"]
        h = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        f = lp["ffn"]
        x = x + _gelu(h @ f["w1"] + f["b1"]) @ f["w2"] + f["b2"]
        return x, None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = _ln(x[:, 0], p["final_ln_g"], p["final_ln_b"], eps)
    return x @ p["head"]


class ReferenceModel:
    """Logits of chosen frames, computed in blocks of ``block`` frames."""

    def __init__(self, params: dict, cfg: dict, keep: int, block: int = 32):
        self.params, self.cfg, self.keep, self.block = params, cfg, keep, block
        self._fn = jax.jit(lambda p, x, s: _classify(p, x, s, keep, cfg))

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
