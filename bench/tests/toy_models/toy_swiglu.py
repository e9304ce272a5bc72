"""A model whose block differs from ``opto_vit``'s, built from the shared
pieces only: a SwiGLU FFN (``w12`` maps d to two halves of ``d_ff``, the
hidden is ``silu(x1) * x2``, ``w3`` maps back), LayerScale on both
residual branches, and one register token after [cls] that has no
position embedding and is never pruned. The harness takes its weights,
forward pass and op counts from here; the program does not serve it."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import ops
from bench.models import opto_vit
from bench.reference import (attention, dense, init_mgnet, ln, mgnet_scores,
                             near, patches, top_patches)

KERNELS = (ops.MATMUL, "flash_attention_masked", "fused_swiglu")
TINY = opto_vit.TINY
arch_config = opto_vit.arch_config
PREFIX = 2                      # [cls] and the register


def init_params(key, cfg: dict) -> dict:
    d, dff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    ks = iter(jax.random.split(key, 48))
    he = float(np.sqrt(2.0))

    def nk():
        return next(ks)

    blocks = {
        "ln1_g": near(nk(), (L, d), 1.0, 0.1),
        "ln1_b": near(nk(), (L, d), 0.0, 0.02),
        "attn": {name: dense(nk(), d, (L, d, d), he)
                 for name in ("wq", "wk", "wv", "wo")},
        "ls1": near(nk(), (L, d), 0.5, 0.1),
        "ln2_g": near(nk(), (L, d), 1.0, 0.1),
        "ln2_b": near(nk(), (L, d), 0.0, 0.02),
        "ffn": {"w12": dense(nk(), d, (L, d, 2 * dff), he),
                "w3": dense(nk(), dff, (L, dff, d), he)},
        "ls2": near(nk(), (L, d), 0.5, 0.1),
    }
    return {
        "patch_embed": {"w": dense(nk(), p_in, (p_in, d), he),
                        "b": near(nk(), (d,), 0.0, 0.02)},
        "cls": near(nk(), (1, 1, d), 0.0, 0.02),
        "reg": near(nk(), (1, PREFIX - 1, d), 0.0, 0.02),
        "pos": near(nk(), (1, n + 1, d), 0.0, 0.02),
        "blocks": blocks,
        "final_ln_g": near(nk(), (d,), 1.0, 0.1),
        "final_ln_b": near(nk(), (d,), 0.0, 0.02),
        "head": dense(nk(), d, (d, cfg["n_classes"]), he),
        "mgnet": init_mgnet(nk, cfg),
    }


def classify(p, images, score_images, keep, cfg):
    eps = cfg["norm_eps"]
    order = top_patches(mgnet_scores(p["mgnet"], score_images, cfg), keep)
    x = patches(images, cfg["patch"]) @ p["patch_embed"]["w"] \
        + p["patch_embed"]["b"] + p["pos"][:, 1:]
    x = jnp.take_along_axis(x, order[:, :, None], axis=1)
    b, _, d = x.shape
    prefix = jnp.concatenate([p["cls"] + p["pos"][:, :1], p["reg"]], axis=1)
    x = jnp.concatenate([jnp.broadcast_to(prefix, (b, PREFIX, d)), x], axis=1)

    def layer(x, lp):
        h = ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        a = lp["attn"]
        x = x + lp["ls1"] * (attention(h, a["wq"], a["wk"], a["wv"],
                                       cfg["n_heads"]) @ a["wo"])
        h = ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        x1, x2 = jnp.split(h @ lp["ffn"]["w12"], 2, axis=-1)
        x = x + lp["ls2"] * ((jax.nn.silu(x1) * x2) @ lp["ffn"]["w3"])
        return x, None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    return ln(x[:, 0], p["final_ln_g"], p["final_ln_b"], eps) @ p["head"]


def frame_work(cfg: dict, frames: int, scored: int, flushes) -> ops.Work:
    d, dff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    w = ops.Work()
    w.merge(ops.embed_work(cfg, frames))
    w.merge(ops.mgnet_work(cfg, scored))
    for kept, n_real in flushes:
        t = kept + PREFIX
        rows = n_real * t
        for _ in range(4):                   # q, k, v, o projections
            w.add(ops.MATMUL, ops.matmul_int8(rows, d, d), L)
        w.add("flash_attention_masked", ops.attention_float(t, d),
              n_real * L)
        glu = ops.Tally(int8_ops=2.0 * rows * d * 2 * dff
                        + 2.0 * rows * dff * d,
                        bytes=rows * d + 3 * d * dff + ops.F32 * rows * d)
        w.add("fused_swiglu", glu, L)
        w.add(ops.MATMUL, ops.matmul_int8(n_real, d, cfg["n_classes"]))
    return w
