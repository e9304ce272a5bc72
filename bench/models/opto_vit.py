"""Opto-ViT (arXiv:2507.07044): a pre-LN ViT encoder (ViT,
arXiv:2010.11929) over the patches MGNet keeps, with one [cls] token,
learned absolute positions, a GELU MLP and a linear head on the
final-normed [cls]. The model of ``opto-vit-base-224`` and
``opto-vit-large-224``.

Departures from the published ViT, each also the program's stated
equation: GELU is the tanh form, LayerNorm's epsilon is the
configuration's ``norm_eps``, the classifier has no bias.

Kernels: ``photonic_matmul`` (every int8 linear: patch embed, MGNet, the
encoder's q/k/v/o projections and the head), ``flash_attention_masked``
(the encoder's softmax(QK^T)V core) and ``fused_ffn`` (the encoder's
GELU MLP, each of its two matmuls counted once: the kernel's recompute
of ``x @ w1`` is not work the frame needs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import ops
from bench.reference import (attention, dense, gelu, init_mgnet, ln,
                             mgnet_scores, near, patches, top_patches)

KERNELS = (ops.MATMUL, "flash_attention_masked", "fused_ffn")
# Opto-ViT Tiny's widths at 2 layers and 64 px (16 patches); the
# 1000-class head is kept, so the gaps compared have the cell's
# distribution of near ties
TINY = {"n_layers": 2, "d_model": 192, "n_heads": 3, "d_ff": 768,
        "img_size": 64}


def arch_config(cfg: dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ArchConfig
    b = cfg["backends"]
    return ArchConfig(
        name=cfg["name"], family="vit", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        kv_heads=cfg["n_heads"], d_ff=cfg["d_ff"], vocab=0,
        img_size=cfg["img_size"], patch=cfg["patch"],
        quant_bits=cfg["quant_bits"], mgnet=True,
        mgnet_embed=cfg["mgnet_embed"], mgnet_heads=cfg["mgnet_heads"],
        norm_eps=cfg["norm_eps"], remat=False,
        matmul_backend=b["matmul"], attn_backend=b["attn"],
        ffn_backend=b["ffn"])


def init_params(key, cfg: dict) -> dict:
    """Random float32 weights in the program's layout. Biases, LayerNorm
    gains and shifts are drawn too (not zeros and ones), so that the
    comparison covers them."""
    d, dff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    ks = iter(jax.random.split(key, 40))
    he = float(np.sqrt(2.0))

    def nk():
        return next(ks)

    # the order of the draws fixes every array a seed gives
    blocks = {
        "ln1_g": near(nk(), (L, d), 1.0, 0.1),
        "ln1_b": near(nk(), (L, d), 0.0, 0.02),
        "attn": {name: dense(nk(), d, (L, d, d), he)
                 for name in ("wq", "wk", "wv", "wo")},
        "ln2_g": near(nk(), (L, d), 1.0, 0.1),
        "ln2_b": near(nk(), (L, d), 0.0, 0.02),
        "ffn": {"w1": dense(nk(), d, (L, d, dff), he),
                "b1": near(nk(), (L, dff), 0.0, 0.02),
                "w2": dense(nk(), dff, (L, dff, d), he),
                "b2": near(nk(), (L, d), 0.0, 0.02)},
    }
    mgnet = init_mgnet(nk, cfg)
    return {
        "patch_embed": {"w": dense(nk(), p_in, (p_in, d), he),
                        "b": near(nk(), (d,), 0.0, 0.02)},
        "cls": near(nk(), (1, 1, d), 0.0, 0.02),
        "pos": near(nk(), (1, n + 1, d), 0.0, 0.02),
        "blocks": blocks,
        "final_ln_g": near(nk(), (d,), 1.0, 0.1),
        "final_ln_b": near(nk(), (d,), 0.0, 0.02),
        "head": dense(nk(), d, (d, cfg["n_classes"]), he),
        "mgnet": mgnet,
    }


def classify(p, images, score_images, keep, cfg):
    """Logits of ``images`` whose kept patches are chosen by the region
    scores of ``score_images`` (the frames whose scores they reuse)."""
    eps = cfg["norm_eps"]
    order = top_patches(mgnet_scores(p["mgnet"], score_images, cfg), keep)
    x = patches(images, cfg["patch"]) @ p["patch_embed"]["w"] \
        + p["patch_embed"]["b"] + p["pos"][:, 1:]
    x = jnp.take_along_axis(x, order[:, :, None], axis=1)
    b, _, d = x.shape
    cls = jnp.broadcast_to(p["cls"] + p["pos"][:, :1], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1)

    def layer(x, lp):
        h = ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        a = lp["attn"]
        x = x + attention(h, a["wq"], a["wk"], a["wv"],
                          cfg["n_heads"]) @ a["wo"]
        h = ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        f = lp["ffn"]
        x = x + gelu(h @ f["w1"] + f["b1"]) @ f["w2"] + f["b2"]
        return x, None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = ln(x[:, 0], p["final_ln_g"], p["final_ln_b"], eps)
    return x @ p["head"]


def encode_work(cfg: dict, kept: int, frames: int) -> ops.Work:
    """Encoder + head for ``frames`` real frames of ``kept`` patches each
    (the [cls] token rides along)."""
    d, dff, layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    t = kept + 1
    rows = frames * t
    w = ops.Work()
    for _ in range(4):                       # q, k, v, o projections
        w.add(ops.MATMUL, ops.matmul_int8(rows, d, d), layers)
    w.add("flash_attention_masked", ops.attention_float(t, d),
          frames * layers)
    ffn = ops.Tally(int8_ops=2.0 * 2.0 * rows * d * dff,
                    bytes=rows * d + 2 * d * dff
                    + ops.F32 * (dff + d + rows * d))
    w.add("fused_ffn", ffn, layers)
    w.add(ops.MATMUL, ops.matmul_int8(frames, d, cfg["n_classes"]))
    return w


def frame_work(cfg: dict, frames: int, scored: int, flushes) -> ops.Work:
    """Every frame's patch embed, MGNet on the ``scored`` frames, and the
    encoder and head of each flush (kept, real frames)."""
    w = ops.Work()
    w.merge(ops.embed_work(cfg, frames))
    w.merge(ops.mgnet_work(cfg, scored))
    for kept, n_real in flushes:
        w.merge(encode_work(cfg, kept, n_real))
    return w
