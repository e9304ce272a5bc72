"""Pallas TPU kernel: fused int8 photonic GELU-MLP (w1 + bias + GELU +
requant + w2 in one kernel).

The encoder FFN is ~2/3 of ViT FLOPs (Opto-ViT Sec. IV), and on the
composed path it runs as two independent ``photonic_matmul_prequant``
dispatches with a float GELU round-trip between them: the ``(B*S, d_ff)``
hidden activation is dequantized to float, written to HBM, read back,
activated, reduced for a fresh absmax scale, requantized and written again
before the second matmul ever starts. Once the MACs are optical that
inter-op traffic — not the matmuls — is the serving bottleneck
(Lightening-Transformer's fused DPTC dataflow makes the same argument).

This kernel keeps the hidden state in VMEM end to end:

  * grid = (2, M/bm): a **two-phase walk** over row blocks. Phase 0
    computes each block's w1-matmul + bias + GELU entirely in VMEM and
    folds its |hidden| maximum into an SMEM running scalar — after the
    phase-0 sweep that scalar *is* the per-tensor absmax the composed
    path computes on the HBM-resident hidden tensor (max is exact, so
    the block-max-of-maxes is bit-identical to the global reduction).
  * phase 1 recomputes the block (activations stream from VMEM-resident
    x; nothing is re-read from HBM), requantizes it with the now-final
    scale — the same ``core.quant.quantize`` arithmetic — and feeds the
    int8 codes straight into the w2 int32 accumulate. Only the final
    (bm, d_out) f32 block is written out.

  Parity contract: the integer accumulates are exact, but the kernel body
  compiles as one unit, so the compiler may contract the dequant multiply
  and bias add into an FMA — a last-ulp freedom on the GELU input that
  the requantization can amplify into a +-1 code flip at a rounding
  boundary. Kernel-vs-twin parity is therefore held to a one-quant-step
  tolerance (the same policy as the flash attention kernel vs its
  oracle); the **XLA twin** is the bit-pinned lowering — identical to the
  composed two-linear dispatch in every execution context
  (tests/test_fused_ffn.py).

  The recompute doubles the w1 MACs but removes 2 x M x d_ff x 4 bytes of
  HBM hidden traffic per call; on the photonic core (and on TPU at serving
  M) the dataflow is bandwidth-bound, so the trade goes the right way.
  Both weight banks ride along whole (int8 codes + per-out-channel scales
  — the quantize-once cache's tuned MR state), which bounds supported
  widths to VMEM: d_ff * (d_in + d_out) int8 + (bm, d_ff) f32 x2 — every
  ViT variant in this repo fits; larger d_ff would need an N-tiled phase 0.

Packed RoI skip: ``live_rows`` (the one-shape serving layout — kept
tokens are a static prefix of the score order) drops fully-pruned token
rows *before the grid is built*, the row-space analogue of the masked
flash kernel skipping pruned KV blocks: dead rows cost zero FLOPs in
both matmuls, the GELU and the absmax, and come back as exact zeros.
Activation scales then reduce over live rows only — identical to running
the composed path on the live slice (the parity contract
tests/test_fused_ffn.py pins).

``fused_ffn_xla`` lowers the same contract for CPU hosts (the Pallas
interpreter is a correctness emulator, not a perf path — same policy as
kernels/flash_attention.py): identical quantize / int32-accumulate /
dequant / GELU / requant ops in one jit, with the same static live-row
slicing. ``fused_ffn`` picks per host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.flash_attention import _pad_axis

__all__ = ["fused_ffn_kernel", "fused_ffn_int8", "fused_ffn_xla",
           "fused_ffn", "fused_ffn_sharded"]


def _bits_pair(bits) -> tuple[int, int]:
    """Static (w1 width, w2 width) from an int or pair — mixed-precision
    bit plans may cache the two banks at different widths. The input
    activation quantizes at w1's width (its matmul's operand precision)
    and the hidden state requantizes at w2's, exactly the widths the
    composed two-``linear`` dispatch would use."""
    if isinstance(bits, (tuple, list)):
        b1, b2 = (int(b) for b in bits)
    else:
        b1 = b2 = int(bits)
    if not (2 <= b1 <= 8 and 2 <= b2 <= 8):
        raise ValueError(f"fused FFN bit widths {bits!r} outside [2, 8]")
    return b1, b2


def fused_ffn_kernel(xq_ref, sx_ref, w1_ref, sw1_ref, b1_ref,
                     w2_ref, sw2_ref, o_ref, amax_ref, *,
                     bm: int, m_eff: int, bits: int, dt):
    """One (phase, row-block) step of the fused FFN walk.

    Grid (2, M/bm). xq (bm, K1) int8; sx (1, 1) f32 per-tensor activation
    scale; w1 (K1, dff) int8 + sw1 (1, dff) f32 + b1 (1, dff) dt;
    w2 (dff, dout) int8 + sw2 (1, dout) f32; o (bm, dout) f32;
    amax (1, 1) f32 SMEM — the running hidden-absmax, alive across the
    whole sequential grid. ``m_eff`` masks padded rows out of the absmax
    (their x rows are zero, but bias + GELU would still leak a nonzero
    |gelu(b1)| into the scale); ``dt`` is the caller's activation dtype so
    every cast lands exactly where the composed path casts. ``bits`` is
    the *hidden requant* width — w2's cached width under a mixed plan;
    the incoming xq codes were already quantized at w1's width outside.
    """
    phase = pl.program_id(0)
    mi = pl.program_id(1)
    row0 = mi * bm
    _, qmax = quant.quant_range(bits)
    inv_qmax = jnp.float32(1.0 / qmax)

    def hidden():
        # w1 int32 accumulate + dequant epilogue + bias + GELU, all in
        # VMEM — op-for-op the composed linear -> gelu prologue.
        acc = jax.lax.dot_general(xq_ref[...], w1_ref[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        h = (acc.astype(jnp.float32) * sx_ref[0, 0]
             * sw1_ref[0, :][None, :]).astype(dt)
        h = h + b1_ref[0, :][None, :]
        return jax.nn.gelu(h.astype(jnp.float32)).astype(dt)

    @pl.when(jnp.logical_and(phase == 0, mi == 0))
    def _init():
        amax_ref[0, 0] = 0.0

    @pl.when(jnp.logical_and(phase == 0, row0 < m_eff))
    def _scan_absmax():
        g = hidden()
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        live = jnp.where(rows < m_eff, jnp.abs(g).astype(jnp.float32), 0.0)
        amax_ref[0, 0] = jnp.maximum(amax_ref[0, 0], jnp.max(live))

    @pl.when(jnp.logical_and(phase == 1, row0 < m_eff))
    def _requant_matmul2():
        g = hidden()                                   # VMEM recompute
        scale2 = jnp.maximum(amax_ref[0, 0], 1e-8) * inv_qmax
        hq = jnp.clip(jnp.round(g.astype(jnp.float32) / scale2),
                      -qmax, qmax).astype(jnp.int8)
        acc2 = jax.lax.dot_general(hq, w2_ref[...],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        o_ref[...] = (acc2.astype(jnp.float32) * scale2
                      * sw2_ref[0, :][None, :])

    @pl.when(jnp.logical_and(phase == 1, row0 >= m_eff))
    def _dead_block():
        o_ref[...] = jnp.zeros_like(o_ref)


def _slice_live(x: jax.Array, live_rows: int | None) -> tuple[jax.Array, int]:
    """Static packed-skip: drop the dead token tail (axis -2) before any
    FLOP is spent — rows are the one-shape score order, so kept rows are a
    prefix. Returns (live slice, live count)."""
    n = x.shape[-2]
    if live_rows is None:
        return x, n
    lv = max(0, min(n, int(live_rows)))
    return x[..., :lv, :], lv


def _restore_dead(y: jax.Array, n: int) -> jax.Array:
    """Zero-fill the dead tail back to the caller's row count: pruned
    rows come back as exact zeros (the residual add then leaves their
    stream state untouched — they are never read as attention keys)."""
    if y.shape[-2] == n:
        return y
    pad = [(0, 0)] * y.ndim
    pad[-2] = (0, n - y.shape[-2])
    return jnp.pad(y, pad)


def fused_ffn_int8(x: jax.Array, w1q: jax.Array, sw1: jax.Array,
                   b1: jax.Array, w2q: jax.Array, sw2: jax.Array,
                   b2: jax.Array, *, bits=8,
                   live_rows: int | None = None, bm: int = 128,
                   interpret: bool = True) -> jax.Array:
    """The Pallas lowering. x (..., n, d_in) float; w1q (d_in, d_ff) int8 +
    sw1 (d_ff,) f32 + b1 (d_ff,); w2q (d_ff, d_out) int8 + sw2 (d_out,)
    f32 + b2 (d_out,). Returns (..., n, d_out) in x.dtype. ``bits`` is an
    int or a (w1, w2) pair (mixed-precision plans — see ``_bits_pair``).
    ``live_rows`` statically prunes the token axis (see module docstring);
    shapes need not be block multiples — operands are padded to the
    128-aligned grid and the result sliced back.
    """
    bits1, bits2 = _bits_pair(bits)
    n_tokens = x.shape[-2]
    xl, lv = _slice_live(x, live_rows)
    if lv == 0:
        return jnp.zeros(x.shape[:-1] + (w2q.shape[1],), x.dtype)
    lead = xl.shape[:-1]
    k1, dff = w1q.shape
    dff2, dout = w2q.shape
    assert xl.shape[-1] == k1 and dff == dff2, (x.shape, w1q.shape, w2q.shape)

    x2 = xl.reshape(-1, k1).astype(jnp.float32)
    m = x2.shape[0]
    sx = quant.absmax_scale(x2, bits=bits1)
    xq = quant.quantize(x2, sx, bits=bits1)

    xq = _pad_axis(_pad_axis(xq, 0, bm), 1, 128)
    w1p = _pad_axis(_pad_axis(w1q, 0, 128), 1, 128)
    w2p = _pad_axis(_pad_axis(w2q, 0, 128), 1, 128)
    sw1p = _pad_axis(sw1.reshape(1, -1), 1, 128)
    sw2p = _pad_axis(sw2.reshape(1, -1), 1, 128)
    b1p = _pad_axis(b1.reshape(1, -1), 1, 128)
    k1p, dffp = w1p.shape
    doutp = w2p.shape[1]

    grid = (2, xq.shape[0] // bm)
    kern = functools.partial(fused_ffn_kernel, bm=bm, m_eff=m, bits=bits2,
                             dt=x.dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k1p), lambda p, i: (i, 0)),
            pl.BlockSpec((1, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((k1p, dffp), lambda p, i: (0, 0)),
            pl.BlockSpec((1, dffp), lambda p, i: (0, 0)),
            pl.BlockSpec((1, dffp), lambda p, i: (0, 0)),
            pl.BlockSpec((dffp, doutp), lambda p, i: (0, 0)),
            pl.BlockSpec((1, doutp), lambda p, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, doutp), lambda p, i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xq.shape[0], doutp), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        interpret=interpret,
        name="fused_ffn",
    )(xq, sx.reshape(1, 1), w1p, sw1p, b1p, w2p, sw2p)
    y = out[:m, :dout].astype(x.dtype) + b2
    return _restore_dead(y.reshape(*lead, dout), n_tokens)


def _dequant_epilogue_kernel(acc_ref, sx_ref, sw_ref, o_ref):
    """Per-tensor x per-out-channel dequant of an int32 accumulate block —
    the exact epilogue of kernels/photonic_matmul.py, as its own kernel."""
    o_ref[...] = (acc_ref[...].astype(jnp.float32) * sx_ref[0, 0]
                  * sw_ref[0, :][None, :])


def _dequant_epilogue(acc: jax.Array, sx: jax.Array, sw: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """Dequantize (M, N) int32 -> f32 through a two-block Pallas walk.

    Running the epilogue as a (gridded) kernel is a numerics requirement,
    not a flourish: the composed reference dequantizes *inside*
    ``photonic_matmul_int8``'s grid loop, so the caller's bias add can
    never contract with the final scale multiply. Inlined into one flat
    XLA graph the CPU backend emits an FMA for that multiply-add (it even
    deletes an ``optimization_barrier`` placed between them) — a 1-ulp
    divergence the downstream requantization amplifies into code flips.
    The grid loop is the same fusion boundary the reference has; two row
    blocks keep it a loop at every M (a single-step grid lowers to
    straight-line HLO that XLA sees through).

    On CPU (``interpret=True``) this is the bit-parity anchor above. On
    TPU the same walk compiles, with each row block rounded up to the
    8-row sublane tile the chip's block rule asks for.
    """
    m, n = acc.shape
    bm = -(-m // 2)
    if not interpret:
        bm = -(-bm // 8) * 8
    accp = _pad_axis(acc, 0, 2 * bm)
    out = pl.pallas_call(
        _dequant_epilogue_kernel,
        grid=(2,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * bm, n), jnp.float32),
        interpret=interpret,
        name="dequant_epilogue",
    )(accp, sx.reshape(1, 1), sw.reshape(1, -1))
    return out[:m]


def _int8_linear_xla(x2: jax.Array, wq: jax.Array, sw: jax.Array, *,
                     bits: int) -> jax.Array:
    """quantize -> int32 accumulate -> dequant, op-for-op the dataflow of
    ``photonic_matmul_prequant`` with the matmul lowered to an XLA integer
    dot (the CPU perf path) and the dequant as the Pallas epilogue kernel
    (the bit-parity anchor — see ``_dequant_epilogue``)."""
    sx = quant.absmax_scale(x2, bits=bits)
    xq = quant.quantize(x2, sx, bits=bits)
    acc = jax.lax.dot_general(xq.astype(jnp.int32), wq.astype(jnp.int32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return _dequant_epilogue(acc, sx, sw, interpret=True)


def fused_ffn_xla(x: jax.Array, w1q: jax.Array, sw1: jax.Array,
                  b1: jax.Array, w2q: jax.Array, sw2: jax.Array,
                  b2: jax.Array, *, bits=8,
                  live_rows: int | None = None) -> jax.Array:
    """XLA lowering of ``fused_ffn_int8`` (same shapes/semantics/codes).

    One jit, no dispatch boundary between the matmuls: XLA fuses the
    dequant -> bias -> GELU -> requant chain element-wise between the two
    integer dots, so the hidden tensor never round-trips through a
    dispatch edge. The kernel's grid-level row skip shows up as the same
    **static packed skip** the masked-attention XLA twin uses: a
    Python-int ``live_rows`` slices the dead token tail away before any
    FLOP — both matmuls, the GELU and both absmax reductions see only
    live rows. Bit-identical to the composed two-linear photonic path on
    the live slice (tests/test_fused_ffn.py) — per matmul at its own
    width when ``bits`` is a (w1, w2) pair.
    """
    bits1, bits2 = _bits_pair(bits)
    n_tokens = x.shape[-2]
    xl, lv = _slice_live(x, live_rows)
    if lv == 0:
        return jnp.zeros(x.shape[:-1] + (w2q.shape[1],), x.dtype)
    lead = xl.shape[:-1]
    dout = w2q.shape[1]
    x2 = xl.reshape(-1, x.shape[-1]).astype(jnp.float32)
    h = _int8_linear_xla(x2, w1q, sw1, bits=bits1).astype(x.dtype) + b1
    g = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    y = _int8_linear_xla(g.astype(jnp.float32), w2q, sw2,
                         bits=bits2).astype(x.dtype) + b2
    return _restore_dead(y.reshape(*lead, dout), n_tokens)


def _int8_linear_sharded(x2: jax.Array, wq: jax.Array, sw: jax.Array, *,
                         bits: int, scale_axes, interpret: bool,
                         psum_axis: str | None = None) -> jax.Array:
    """``_int8_linear_xla`` for use *inside* ``shard_map``: the activation
    absmax scale is pmax'd over ``scale_axes`` (so every shard quantizes
    with the scale the unsharded launch would compute — the bitwise-parity
    anchor), and an optional ``psum_axis`` reduces row-sharded partial
    accumulates exactly in int32 before the dequant epilogue. With wq
    column-sharded (no psum) the output holds this shard's columns of the
    full result; with wq row-sharded + psum it holds the full contraction,
    replicated over the model axis — either way bit-identical to the
    corresponding slice of the unsharded ``_int8_linear_xla``."""
    from repro.distributed.collectives import (exact_int_psum,
                                               replicated_absmax_scale)
    sx = replicated_absmax_scale(x2, bits, scale_axes)
    xq = quant.quantize(x2, sx, bits=bits)
    acc = jax.lax.dot_general(xq.astype(jnp.int32), wq.astype(jnp.int32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    if psum_axis is not None:
        acc = exact_int_psum(acc, psum_axis)
    return _dequant_epilogue(acc, sx, sw, interpret=interpret)


def fused_ffn_sharded(x: jax.Array, w1q: jax.Array, sw1: jax.Array,
                      b1: jax.Array, w2q: jax.Array, sw2: jax.Array,
                      b2: jax.Array, *, bits=8,
                      live_rows: int | None = None,
                      model_axis: str | None = "model",
                      scale_axes=("data", "model"),
                      interpret: bool) -> jax.Array:
    """``fused_ffn_xla`` under ``shard_map`` over the d_ff (model) axis.

    Per-shard operands: w1q (d_in, d_ff/M) columns + sw1/b1 (d_ff/M,),
    w2q (d_ff/M, d_out) rows + *full* sw2 (d_out,) / b2 (d_out,). The
    hidden activation lives column-sharded (each shard runs its GELU on
    its own d_ff slice); the only cross-shard traffic is two scalar pmaxes
    (activation absmax scopes stay global — ``replicated_absmax_scale``)
    and one int32 psum of the w2 partial accumulates (exact). Every float
    op then sees bit-identical inputs to the unsharded twin, including
    the Pallas dequant epilogue (the FMA fusion boundary), so the result
    is bitwise-equal to ``fused_ffn_xla`` on the gathered operands.
    ``scale_axes`` must name every mesh axis the token rows are split
    over *plus* the model axis (batch-sharded callers pass both). On a
    data-only mesh ``model_axis=None``: each shard holds all of d_ff and
    there is no partial sum to reduce.
    ``interpret`` is the caller's ``ExecPolicy.interpret``: it picks the
    CPU or TPU form of the dequant epilogue."""
    bits1, bits2 = _bits_pair(bits)
    n_tokens = x.shape[-2]
    xl, lv = _slice_live(x, live_rows)
    if lv == 0:
        return jnp.zeros(x.shape[:-1] + (w2q.shape[1],), x.dtype)
    lead = xl.shape[:-1]
    dout = w2q.shape[1]
    x2 = xl.reshape(-1, x.shape[-1]).astype(jnp.float32)
    h = _int8_linear_sharded(x2, w1q, sw1, bits=bits1, scale_axes=scale_axes,
                             interpret=interpret).astype(x.dtype) + b1
    g = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    y = _int8_linear_sharded(g.astype(jnp.float32), w2q, sw2, bits=bits2,
                             scale_axes=scale_axes, interpret=interpret,
                             psum_axis=model_axis).astype(x.dtype) + b2
    return _restore_dead(y.reshape(*lead, dout), n_tokens)


def fused_ffn(x: jax.Array, w1q: jax.Array, sw1: jax.Array, b1: jax.Array,
              w2q: jax.Array, sw2: jax.Array, b2: jax.Array, *,
              bits=8, live_rows: int | None = None, bm: int = 128,
              interpret: bool) -> jax.Array:
    """The fused int8 FFN, lowered for the host it runs on: the Pallas
    kernel when compiling for TPU (``interpret=False``), the XLA twin on
    CPU hosts (the serving hot path's FFN entry point, dispatched by
    ``core.backend.ffn``). Deliberately *not* jitted here: the hot path
    always runs under its caller's jit (the single-jit encoder step in
    models/vit.py or the serving engine's encode), and an extra nested
    jit would only change fusion boundaries against the composed
    reference."""
    if interpret:
        return fused_ffn_xla(x, w1q, sw1, b1, w2q, sw2, b2, bits=bits,
                             live_rows=live_rows)
    return fused_ffn_int8(x, w1q, sw1, b1, w2q, sw2, b2, bits=bits,
                          live_rows=live_rows, bm=bm, interpret=False)
