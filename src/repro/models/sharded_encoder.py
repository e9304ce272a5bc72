"""Sharded single-jit encoder: the fused serving hot path under
``shard_map`` over a serving mesh — 2-D ("data", "model") or data-only
("data",).

The fused encoder in models/vit.py runs the whole trunk as one jit —
fused RoI attention + fused int8 FFN scanned over the stacked layer
weights. This module re-traces exactly that graph *inside* one
``shard_map``. On a TPU that is the only way the encoder runs on more
than one chip at all: XLA cannot partition a Pallas (Mosaic) kernel, so
every kernel must sit in a region where each device's shard is explicit.
A "model" axis additionally lets big ViT variants whose weights (or
activations) outgrow one device keep the single-dispatch serving path:

  * attention heads are embarrassingly parallel: wq/wk/wv **column-shard**
    over "model" (output columns are head-major), each shard runs the
    flash-attention core on its own head group, the merged head outputs
    all-gather (exact data movement) and the wo projection runs whole on
    every shard;
  * the FFN hidden dim column-shards w1 / row-shards w2 with one int32
    psum over the d_ff partial sums (kernels/fused_ffn.fused_ffn_sharded);
  * the encode batch still splits over "data" whenever the flush batch
    divides the axis (otherwise it replicates — both are bitwise-safe).

Bitwise parity with the unsharded fused encoder is a *construction*, not
a tolerance: every per-launch activation absmax scope is restored to the
global tensor via ``collectives.replicated_absmax_scale`` (max is exact),
the FFN's int32 partial-sum reduction is lossless (``exact_int_psum``),
and every dequant runs *where the unsharded twin runs it*. That last
point is load-bearing: the attention projections and the head dequantize
**inside** ``photonic_matmul_int8``'s grid loop (the serving path's
kernel), so the sharded trace mirrors ``ops.photonic_matmul_prequant``
op-for-op with only the absmax scope widened (``_pallas_proj``) — an XLA
int-dot + detached epilogue computes the same math but fuses differently
against the surrounding graph (a 1-ulp FMA-class divergence the
downstream requant amplifies into code flips). The FFN reference is the
XLA twin (``fused_ffn_xla``), so there ``fused_ffn_sharded`` keeps the
int-dot + ``_dequant_epilogue`` construction. wo is *not* row-sharded:
its dequant lives inside the kernel, so a row split would need an int32
psum between accumulate and dequant — unreachable without changing the
reference graph; all-gathering the (small) merged head activations and
replicating the wo matmul keeps the bitwise contract instead. Each shard
therefore computes bit-identical slices of the very arrays the 1-device
path holds, and the assembled logits match bitwise
(tests/test_multistream.py pins this in a forced-4-device subprocess).

Weights enter the shard_map as plain {codes, scale} dicts (QuantizedWeight
is unwrapped inside the jit, raw wo/head leaves are resolved there with
the same quantize-once arithmetic the unsharded dispatch applies), so the
in_specs tree stays a static literal per layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import quant
from repro.core.backend import QuantizedWeight, _resolve_wq, _weight_bits
from repro.distributed.collectives import replicated_absmax_scale
from repro.kernels.flash_attention import fused_masked_attention
from repro.kernels.fused_ffn import fused_ffn_sharded
from repro.kernels.ops import pad_to
from repro.kernels.photonic_matmul import photonic_matmul_int8
from repro.models.layers import ExecPolicy, layernorm

__all__ = ["sharded_encode", "sharded_encode_ineligible_reason",
           "sharded_encoder_cache_size"]

def _pallas_proj(x2: jnp.ndarray, wq: jnp.ndarray, sw: jnp.ndarray, *,
                 bits: int, interpret: bool, scale_axes) -> jnp.ndarray:
    """``ops.photonic_matmul_prequant`` inlined for use inside
    ``shard_map``: same quantize -> pad -> ``photonic_matmul_int8`` (with
    its in-kernel dequant) dataflow, with the per-launch activation absmax
    scope widened from the local shard to the global tensor
    (``replicated_absmax_scale`` — a pmax, exact). Per-column outputs are
    independent in the kernel, so with ``wq`` holding this shard's column
    slice the result is bitwise the matching column slice of the unsharded
    call; with ``wq`` whole (replicated) it is the whole unsharded result.

    x2 (M, K) f32; wq (K, N) int8 codes; sw (N,) f32. Returns (M, N) f32.
    """
    m, n = x2.shape[0], wq.shape[1]
    sx = replicated_absmax_scale(x2, bits, scale_axes)
    xq = quant.quantize(x2, sx, bits=bits)
    xqp = pad_to(pad_to(xq, 128, 0), 128, 1)
    wqp = pad_to(pad_to(wq, 128, 0), 128, 1)
    swp = pad_to(sw, 128, 0)
    out = photonic_matmul_int8(xqp, wqp, sx.reshape(()), swp,
                               bm=128, bn=128, bk=128, interpret=interpret)
    return out[:m, :n]


def _encoder_bits(params: dict, policy: ExecPolicy) -> dict[str, int]:
    """Static per-weight bit widths for the sharded trace. Raises
    ValueError (the ineligibility reason) when any stacked weight carries
    a per-layer bits tuple — the sharded encoder compiles ONE scan, so a
    mixed plan would need the segmented-scan machinery sliced per run;
    mixed plans fall back to the unsharded fused path instead."""
    blocks = params["blocks"]
    bits = {}
    for name in ("wq", "wk", "wv", "wo"):
        bits[name] = _weight_bits(blocks["attn"][name], policy)
    for name in ("w1", "w2"):
        bits[name] = _weight_bits(blocks["ffn"][name], policy)
    bits["head"] = _weight_bits(params["head"], policy)
    return bits


def sharded_encode_ineligible_reason(params: dict, cfg: ArchConfig,
                                     policy: ExecPolicy, ctx) -> str | None:
    """None when the fused encoder can additionally run model-sharded
    under ``ctx`` (callers check fused eligibility first), else a
    human-readable reason for staying on the unsharded fused jit."""
    if ctx is None:
        return "no sharding context installed"
    mesh = ctx.mesh
    axes = tuple(mesh.axis_names)
    if axes not in (("data",), ("data", "model")):
        return (f"mesh axes {axes!r} are not a serving layout: ('data',) "
                f"or ('data', 'model') (launch.mesh.make_serving_mesh)")
    if mesh.size < 2:
        return "the mesh has one device — nothing to shard"
    m = _model_shards(mesh)
    if cfg.n_heads % m:
        return (f"n_heads={cfg.n_heads} not divisible by the model axis "
                f"({m}) — heads cannot split evenly")
    if cfg.d_ff % m:
        return (f"d_ff={cfg.d_ff} not divisible by the model axis ({m}) — "
                f"the FFN hidden dim cannot split evenly")
    try:
        _encoder_bits(params, policy)
    except ValueError as e:
        return str(e)
    return None


def _model_shards(mesh) -> int:
    """Size of the mesh's "model" axis; 1 on a data-only mesh."""
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def _qw_dict(w, bits: int) -> dict:
    """{int8 codes, f32 scale} for a cached or raw weight — the same
    ``_resolve_wq`` arithmetic the unsharded 2-D dispatch applies, run
    inside the jit so raw stacked leaves (wo, head) quantize identically
    to the per-layer slices the reference scan resolves."""
    wq, sw = _resolve_wq(w, bits)
    return {"wq": wq, "scale": sw}


def _enc_tree(params: dict, bits: dict[str, int]) -> dict:
    """The encoder subtree the shard_map consumes: QuantizedWeight leaves
    unwrapped to plain dicts (pytree aux data cannot ride through
    in_specs), cls + its pos row pre-summed (elementwise — bitwise equal
    to broadcasting then adding)."""
    blocks = params["blocks"]
    attn = blocks["attn"]
    ffn = blocks["ffn"]
    return {
        "cls_pos": params["cls"] + params["pos"][:, :1],
        "blocks": {
            "ln1_g": blocks["ln1_g"], "ln1_b": blocks["ln1_b"],
            "attn": {name: _qw_dict(attn[name], bits[name])
                     for name in ("wq", "wk", "wv", "wo")},
            "ln2_g": blocks["ln2_g"], "ln2_b": blocks["ln2_b"],
            "ffn": {"w1": _qw_dict(ffn["w1"], bits["w1"]),
                    "b1": ffn["b1"],
                    "w2": _qw_dict(ffn["w2"], bits["w2"]),
                    "b2": ffn["b2"]},
        },
        "final_ln_g": params["final_ln_g"],
        "final_ln_b": params["final_ln_b"],
        "head": _qw_dict(params["head"], bits["head"]),
    }


def _enc_specs(model: str | None) -> dict:
    """in_specs tree matching ``_enc_tree``: head-major output columns
    (wq/wk/wv, w1) shard over the ``model`` axis, the w2 contraction rows
    (= d_ff) shard over it with replicated output scales, and everything
    else — including wo, whose in-kernel dequant forbids a row split
    (module docstring) — replicates. Mirrors what MODEL_RULES +
    vit_logical_axes place on the devices, so the dispatch edge moves no
    bytes. ``model=None`` (a data-only mesh) replicates every weight."""
    col = P(None, None, model)         # stacked codes/scales, cols = heads
    row = P(None, model, None)         # stacked codes, rows = d_ff
    rep3 = P(None, None, None)
    rep2 = P(None, None)
    return {
        "cls_pos": rep3,
        "blocks": {
            "ln1_g": rep2, "ln1_b": rep2,
            "attn": {"wq": {"wq": col, "scale": col},
                     "wk": {"wq": col, "scale": col},
                     "wv": {"wq": col, "scale": col},
                     "wo": {"wq": rep3, "scale": rep3}},
            "ln2_g": rep2, "ln2_b": rep2,
            "ffn": {"w1": {"wq": col, "scale": col},
                    "b1": P(None, model),
                    "w2": {"wq": row, "scale": rep3},
                    "b2": rep2},
        },
        "final_ln_g": P(None), "final_ln_b": P(None),
        "head": {"wq": rep2, "scale": rep2},
    }


# (cfg, policy fingerprint, bits signature, kv_len, has_mask, mesh,
#  batch-sharded?) -> jitted sharded encode entry. Same lifecycle as
# models.vit._FUSED_ENCODER_JITS — a handful of entries per process.
_SHARDED_ENCODER_JITS: dict = {}


def sharded_encoder_cache_size() -> int:
    """How many sharded-encoder jits this process built — benches assert
    it grew to prove the sharded path (not a silent fallback) served."""
    return len(_SHARDED_ENCODER_JITS)


def _build_jit(cfg: ArchConfig, policy: ExecPolicy, bits: dict[str, int],
               kv_len: int | None, has_mask: bool, mesh,
               batch_sharded: bool):
    n_heads, d, eps = cfg.n_heads, cfg.d_model, cfg.norm_eps
    model = "model" if "model" in mesh.axis_names else None
    scale_axes = tuple(mesh.axis_names)
    m_shards = _model_shards(mesh)
    h_loc = n_heads // m_shards
    dh = d // n_heads
    d_loc = h_loc * dh
    interpret = policy.interpret
    attn_kv = None if kv_len is None else int(kv_len) + 1   # + live [cls]
    ffn_live = attn_kv

    def body(enc, tokens, mask):
        b, _, _ = tokens.shape
        x = jnp.concatenate(
            [jnp.broadcast_to(enc["cls_pos"], (b, 1, d))
             .astype(tokens.dtype), tokens], axis=1)
        kmask = None
        if mask is not None:
            kmask = jnp.concatenate(
                [jnp.ones((b, 1), mask.dtype), mask], axis=1)

        def step(carry, lp):
            n = carry.shape[1]
            h = layernorm(carry, lp["ln1_g"], lp["ln1_b"], eps)
            x2 = h.astype(jnp.float32).reshape(-1, d)
            qkv = []
            for name in ("wq", "wk", "wv"):
                wd = lp["attn"][name]
                y = _pallas_proj(x2, wd["wq"], wd["scale"].reshape(-1),
                                 bits=bits[name], interpret=interpret,
                                 scale_axes=scale_axes)
                qkv.append(y.reshape(b, n, d_loc).astype(h.dtype)
                           .reshape(b, n, h_loc, dh).transpose(0, 2, 1, 3))
            o = fused_masked_attention(qkv[0], qkv[1], qkv[2], kmask,
                                       kv_len=attn_kv, interpret=interpret)
            merged = o.transpose(0, 2, 1, 3).reshape(b, n, d_loc)
            # exact data movement: every shard assembles the full
            # head-major (b, n, d) activation, then runs the whole wo
            # projection (in-kernel dequant — see module docstring)
            full = (merged if model is None else
                    jax.lax.all_gather(merged, model, axis=2, tiled=True))
            wd = lp["attn"]["wo"]
            ao = _pallas_proj(full.astype(jnp.float32).reshape(-1, d),
                              wd["wq"], wd["scale"].reshape(-1),
                              bits=bits["wo"], interpret=interpret,
                              scale_axes=scale_axes)
            carry = carry + ao.reshape(b, n, d).astype(h.dtype) \
                              .astype(carry.dtype)
            h2 = layernorm(carry, lp["ln2_g"], lp["ln2_b"], eps)
            f = fused_ffn_sharded(
                h2, lp["ffn"]["w1"]["wq"],
                lp["ffn"]["w1"]["scale"].reshape(-1), lp["ffn"]["b1"],
                lp["ffn"]["w2"]["wq"],
                lp["ffn"]["w2"]["scale"].reshape(-1), lp["ffn"]["b2"],
                bits=(bits["w1"], bits["w2"]), live_rows=ffn_live,
                model_axis=model, scale_axes=scale_axes,
                interpret=interpret)
            return carry + f, None

        fn = jax.checkpoint(step) if cfg.remat else step
        x, _ = jax.lax.scan(fn, x, enc["blocks"])
        x = layernorm(x, enc["final_ln_g"], enc["final_ln_b"], eps)
        logits = _pallas_proj(x[:, 0].astype(jnp.float32),
                              enc["head"]["wq"],
                              enc["head"]["scale"].reshape(-1),
                              bits=bits["head"], interpret=interpret,
                              scale_axes=scale_axes)
        return logits.astype(x.dtype)

    tok_spec = P("data", None, None) if batch_sharded else P(None, None, None)
    out_spec = P("data", None) if batch_sharded else P(None, None)
    mask_spec = P("data", None) if batch_sharded else P(None, None)
    specs = _enc_specs(model)

    if has_mask:
        smapped = jax.shard_map(body, mesh=mesh,
                                in_specs=(specs, tok_spec, mask_spec),
                                out_specs=out_spec, check_vma=False)

        def run(params, tokens, patch_mask):
            return smapped(_enc_tree(params, bits), tokens, patch_mask)
    else:
        smapped = jax.shard_map(lambda enc, t: body(enc, t, None),
                                mesh=mesh, in_specs=(specs, tok_spec),
                                out_specs=out_spec, check_vma=False)

        def run(params, tokens, patch_mask):
            return smapped(_enc_tree(params, bits), tokens)

    return jax.jit(run)


def sharded_encode(params: dict, tokens: jnp.ndarray, cfg: ArchConfig,
                   policy: ExecPolicy, patch_mask: jnp.ndarray | None,
                   kv_len: int | None, ctx) -> jnp.ndarray:
    """The model-sharded twin of the fused-encoder jit dispatch in
    models/vit.py. Callers (encode_tokens) have already verified fused +
    sharded eligibility; this resolves the static bit widths, picks the
    batch layout (split over "data" when the flush batch divides it,
    replicated otherwise — the same divisibility fallback ``shard`` and
    the server's ``_place`` apply) and dispatches the cached jit."""
    bits = _encoder_bits(params, policy)
    mesh = ctx.mesh
    batch_sharded = tokens.shape[0] % mesh.shape["data"] == 0
    kv = None if kv_len is None else int(kv_len)
    key = (cfg, policy.fingerprint(), tuple(sorted(bits.items())), kv,
           patch_mask is not None, mesh, batch_sharded)
    fn = _SHARDED_ENCODER_JITS.get(key)
    if fn is None:
        fn = _build_jit(cfg, policy, bits, kv, patch_mask is not None,
                        mesh, batch_sharded)
        _SHARDED_ENCODER_JITS[key] = fn
    return fn(params, tokens, patch_mask)
