"""MGNet: lightweight region-of-interest Mask Generation Network.

Paper §IV "Region of Interest Selection": a single transformer block followed
by a self-attention scoring layer and a linear projection. For each frame:

  1. patchify + embed (patch p=16, embed L=192, 3 heads; the detection
     variant uses 384/6),
  2. one transformer encoder block over [cls] + patch tokens,
  3. attention score  S_cls_attn = q_cls . K^T / sqrt(d)   (Eq. 3),
  4. linear head -> per-patch region scores S_region,
  5. sigmoid + threshold t_reg -> binary patch mask,
  6. trained with BCE against box-derived {0,1} patch labels;
     mask quality measured by mIoU.

Masked patches are dropped *before* the first backbone encoder block. Since a
ViT never mixes patches spatially outside attention, every downstream FLOP of
a dropped patch is saved (linear savings — the paper's key observation).

JIT-compatibility: dynamic patch counts don't trace, so the backbone-facing
API offers two modes:
  * ``mask``   — multiplicative binary masking (shapes static; compute not
    reduced, used for training/accuracy studies),
  * ``topk``   — keep a fixed budget of the k highest-scoring patches
    (shapes static at k; compute *is* reduced; k = ceil((1-skip)*n)).
The hardware energy model consumes the true expected skip ratio either way.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import ExecPolicy, linear

__all__ = ["MGNetConfig", "init_mgnet", "mgnet_logical_axes", "mgnet_scores",
           "mgnet_mask", "select_topk_patches", "mask_iou", "bce_loss",
           "mask_budget", "frame_delta"]


@dataclass(frozen=True)
class MGNetConfig:
    patch: int = 16
    embed: int = 192        # 384 for the detection variant
    heads: int = 3          # 6 for the detection variant
    mlp_ratio: float = 4.0
    t_reg: float = 0.5      # sigmoid threshold for the binary mask
    img_size: int = 96

    @property
    def n_patches(self) -> int:
        return (self.img_size // self.patch) ** 2


def _dense_init(key, shape, scale=None):
    scale = scale or (1.0 / jnp.sqrt(shape[0]))
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_mgnet(key: jax.Array, cfg: MGNetConfig) -> dict:
    """Parameter pytree for MGNet (patch-embed + 1 block + score head)."""
    d = cfg.embed
    n_in = 3 * cfg.patch * cfg.patch
    ks = jax.random.split(key, 12)
    return {
        "patch_embed": {"w": _dense_init(ks[0], (n_in, d)), "b": jnp.zeros((d,))},
        "cls_token": jax.random.normal(ks[1], (1, 1, d)) * 0.02,
        "pos_embed": jax.random.normal(ks[2], (1, cfg.n_patches + 1, d)) * 0.02,
        "block": {
            "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "wqkv": _dense_init(ks[3], (d, 3 * d)),
            "wo": _dense_init(ks[4], (d, d)),
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "w1": _dense_init(ks[5], (d, int(d * cfg.mlp_ratio))),
            "b1": jnp.zeros((int(d * cfg.mlp_ratio),)),
            "w2": _dense_init(ks[6], (int(d * cfg.mlp_ratio), d)),
            "b2": jnp.zeros((d,)),
        },
        # scoring attention (Eq. 3) + linear region head
        "score": {
            "wq": _dense_init(ks[7], (d, d)),
            "wk": _dense_init(ks[8], (d, d)),
            "head_w": _dense_init(ks[9], (cfg.n_patches, cfg.n_patches)),
            "head_b": jnp.zeros((cfg.n_patches,)),
        },
    }


def mgnet_logical_axes() -> dict:
    """Replicated (all-None) sharding-axis tree structurally matching
    ``init_mgnet``'s params — MGNet is tiny, so it is never partitioned, but
    the axis tree must still mirror the param pytree for the annotation
    machinery (models/vit.py::vit_logical_axes)."""
    return {
        "patch_embed": {"w": (None, None), "b": (None,)},
        "cls_token": (None, None, None),
        "pos_embed": (None, None, None),
        "block": {
            "ln1": {"g": (None,), "b": (None,)},
            "wqkv": (None, None),
            "wo": (None, None),
            "ln2": {"g": (None,), "b": (None,)},
            "w1": (None, None), "b1": (None,),
            "w2": (None, None), "b2": (None,),
        },
        "score": {"wq": (None, None), "wk": (None, None),
                  "head_w": (None, None), "head_b": (None,)},
    }


def _ln(x, p, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _mhsa(x, wqkv, wo, heads, policy=None):
    b, n, d = x.shape
    qkv = linear(x, wqkv, policy=policy)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    dh = d // heads
    q = q.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    att = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(dh), axis=-1)
    o = (att @ v).transpose(0, 2, 1, 3).reshape(b, n, d)
    return linear(o, wo, policy=policy)


def patchify(images: jnp.ndarray, patch: int) -> jnp.ndarray:
    """(B, H, W, C) -> (B, n_patches, patch*patch*C)."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def mgnet_scores(params: dict, images: jnp.ndarray, cfg: MGNetConfig,
                 policy: ExecPolicy | None = None) -> jnp.ndarray:
    """Per-patch region scores S_region (pre-sigmoid logits), shape (B, N).

    Every weight matmul routes through the shared ``linear`` backend
    dispatch — on the paper's hardware MGNet runs on the same optical cores
    as the backbone, so it executes under the same policy (photonic w8a8 at
    serve time). Only the q.K^T and att.V activation matmuls stay in float.
    """
    x = linear(patchify(images, cfg.patch), params["patch_embed"]["w"],
               params["patch_embed"]["b"], policy)
    b, n, d = x.shape
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][:, : n + 1]

    blk = params["block"]
    x = x + _mhsa(_ln(x, blk["ln1"]), blk["wqkv"], blk["wo"], cfg.heads,
                  policy)
    h = linear(_ln(x, blk["ln2"]), blk["w1"], blk["b1"], policy)
    x = x + linear(jax.nn.gelu(h), blk["w2"], blk["b2"], policy)

    # Eq. 3: S_cls_attn = q_cls . K^T / sqrt(d) over patch tokens.
    q_cls = linear(x[:, :1], params["score"]["wq"], policy=policy)  # (B,1,d)
    k_pat = linear(x[:, 1:], params["score"]["wk"], policy=policy)  # (B,N,d)
    s_cls = (q_cls @ k_pat.transpose(0, 2, 1))[:, 0] / jnp.sqrt(d)  # (B, N)
    # linear layer with output dim = n_patches -> S_region
    return linear(s_cls, params["score"]["head_w"],
                  params["score"]["head_b"], policy)


def mgnet_mask(params: dict, images: jnp.ndarray, cfg: MGNetConfig,
               policy: ExecPolicy | None = None) -> jnp.ndarray:
    """Binary patch mask (B, N) in {0., 1.}: sigmoid(S_region) > t_reg."""
    s = jax.nn.sigmoid(mgnet_scores(params, images, cfg, policy))
    return (s > cfg.t_reg).astype(jnp.float32)


def select_topk_patches(scores: jnp.ndarray, tokens: jnp.ndarray, keep: int):
    """Static-shape RoI pruning: keep the ``keep`` highest-scoring patches.

    scores: (B, N) region logits; tokens: (B, N, D) patch embeddings.
    Returns (pruned_tokens (B, keep, D), kept_idx (B, keep)).

    Tie-breaking is deterministic: among equal scores the lowest patch index
    wins (stable descending argsort rather than ``lax.top_k``, whose tie
    order is backend-defined). The serving bucket router keys on the kept
    set, so reproducible routing requires reproducible selection.
    """
    idx = jnp.argsort(scores, axis=-1, stable=True, descending=True)
    idx = idx[..., :keep]
    pruned = jnp.take_along_axis(tokens, idx[..., None], axis=1)
    return pruned, idx


def mask_budget(scores, t_reg: float = 0.5):
    """Per-frame kept-patch count implied by the binary mask, shape (B,).

    This is the *token budget* a frame requests from the serving bucket
    ladder: the number of patches whose sigmoid score clears ``t_reg``.
    Accepts numpy or jax scores and stays in that domain — score-driven
    serving routes on the host, on scores pulled once per scored chunk
    (``TemporalMaskCache.host_scores``), and counts in numpy so routes
    are bitwise the host rule's.
    """
    if isinstance(scores, np.ndarray):
        keep = 1.0 / (1.0 + np.exp(-scores.astype(np.float64))) > t_reg
        return keep.sum(axis=-1).astype(np.int32)
    return (jax.nn.sigmoid(scores) > t_reg).sum(axis=-1).astype(jnp.int32)


def frame_delta(frames, ref):
    """Cheap per-frame change signal vs a reference frame, shape (B,).

    Mean absolute pixel difference — the near-sensor trigger for re-running
    MGNet: below a threshold the cached RoI mask is reused (static scene),
    above it (motion / scene cut) the frame is re-scored. O(HW) adds per
    frame, i.e. negligible next to even one MGNet patch-embed matmul.
    Numpy in, numpy out (host-side gating walk); jax in, jax out.
    """
    xp = np if isinstance(frames, np.ndarray) else jnp
    d = xp.abs(frames.astype(xp.float32) - ref.astype(xp.float32))
    return d.mean(axis=tuple(range(1, frames.ndim)))


def mask_iou(pred: jnp.ndarray, gt: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """mIoU between binary masks (B, N) — the paper's mask quality metric."""
    inter = jnp.sum(pred * gt, axis=-1)
    union = jnp.sum(jnp.clip(pred + gt, 0, 1), axis=-1)
    return jnp.mean(inter / (union + eps))


def bce_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Binary cross-entropy on region scores vs box-derived labels."""
    log_p = jax.nn.log_sigmoid(logits)
    log_not_p = jax.nn.log_sigmoid(-logits)
    return -jnp.mean(labels * log_p + (1.0 - labels) * log_not_p)
