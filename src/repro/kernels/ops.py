"""jit'd public wrappers around the Pallas kernels.

``photonic_matmul(x, w)`` is the drop-in float API: it quantizes (absmax,
symmetric — core/quant.py), pads to kernel block multiples, runs the int8
kernel and dequantizes. ``fused_attention`` exposes the flash kernel with
the models/attention.py calling convention (B, S, H, D).

Every wrapper takes ``interpret=``: the Pallas interpreter on CPU hosts,
compiled kernels on TPU. The serving path passes ``ExecPolicy.interpret``,
which follows the platform (``core.backend.interpret_default``). The
serving entry points (``fused_roi_attention_prequant`` here,
``fused_masked_attention``, ``fused_ffn``) have no default for it, so none
of them can silently emulate on a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.flash_attention import (flash_attention,
                                           fused_masked_attention)
from repro.kernels.flash_decode import flash_decode
from repro.kernels.fused_ffn import fused_ffn
from repro.kernels.photonic_matmul import photonic_matmul_int8

__all__ = ["photonic_matmul", "photonic_matmul_prequant",
           "photonic_matmul_prequant_noisy", "fused_attention",
           "fused_roi_attention_prequant", "fused_ffn", "flash_decode",
           "pad_to"]


def pad_to(x, mult, axis):
    r = (-x.shape[axis]) % mult
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "interpret"))
def photonic_matmul_prequant(x: jax.Array, wq: jax.Array, sw: jax.Array, *,
                             bits: int = 8, bm: int = 128, bn: int = 128,
                             bk: int = 128, interpret: bool = True
                             ) -> jax.Array:
    """Serving path for the quantize-once cache: the weight arrives already
    tuned (int8 codes + per-out-channel scale from core/backend.py); only
    the activations are quantized per call.

    x (..., K) float; wq (K, N) int8; sw (N,) f32. Returns (..., N) f32.
    Shapes need not be block multiples — callers' M/K/N are padded to the
    128-aligned kernel grid and the result is sliced back.
    """
    lead = x.shape[:-1]
    k, n = wq.shape
    x2 = x.reshape(-1, k).astype(jnp.float32)
    m = x2.shape[0]

    sx = quant.absmax_scale(x2, bits=bits)
    xq = quant.quantize(x2, sx, bits=bits)

    xq = pad_to(pad_to(xq, bm, 0), bk, 1)
    wqp = pad_to(pad_to(wq, bk, 0), bn, 1)
    swp = pad_to(sw, bn, 0)
    out = photonic_matmul_int8(xq, wqp, sx.reshape(()), swp,
                               bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("bits", "shot_sigma",
                                             "adc_bits", "chunk"))
def photonic_matmul_prequant_noisy(x: jax.Array, wq: jax.Array,
                                   sw: jax.Array, mult: jax.Array,
                                   readout_key: jax.Array, *,
                                   bits: int = 8, shot_sigma: float = 0.0,
                                   adc_bits: int = 0, chunk: int = 32
                                   ) -> jax.Array:
    """Noisy companion of ``photonic_matmul_prequant`` for the interpret-mode
    Pallas serving path.

    The int8 kernel is the *clean digital contract* — a sub-LSB analog
    transmission error cannot ride through integer codes — so noisy
    execution walks the same wavelength-chunk schedule on float codes
    (core/photonic.py: ``analog_accumulate``) with the MR multiplier
    ``mult`` (K, N) applied to the tuned bank, then adds shot noise and an
    optional range-limited ADC requant on the readout. ``mult`` and
    ``readout_key`` are explicit traced arguments: this wrapper is itself
    jitted, so the caller's noise draws must cross the boundary as inputs,
    never as closed-over tracers.
    """
    from repro.core.photonic import analog_accumulate
    lead = x.shape[:-1]
    k, n = wq.shape
    x2 = x.reshape(-1, k).astype(jnp.float32)

    sx = quant.absmax_scale(x2, bits=bits)
    xq = quant.quantize(x2, sx, bits=bits)
    acc = analog_accumulate(xq, wq.astype(jnp.float32) * mult, chunk=chunk)
    y = acc * sx * sw[None, :]
    if shot_sigma > 0.0:
        y = y * (1.0 + shot_sigma * jax.random.normal(readout_key, y.shape))
    if adc_bits:
        s = quant.absmax_scale(y, bits=adc_bits)
        y = quant.dequantize(quant.quantize(y, s, bits=adc_bits), s)
    return y.reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "interpret"))
def photonic_matmul(x: jax.Array, w: jax.Array, *, bits: int = 8,
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = True) -> jax.Array:
    """Float API: quantize both operands -> int8 kernel -> dequantize.

    x (..., K) any float dtype; w (K, N). Returns (..., N) f32.
    """
    w32 = w.astype(jnp.float32)
    sw = quant.absmax_scale(w32, bits=bits, axis=0)[0]
    wq = quant.quantize(w32, sw[None], bits=bits)
    return photonic_matmul_prequant(x, wq, sw, bits=bits, bm=bm, bn=bn,
                                    bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("heads", "kv_len", "bits",
                                             "bq", "bkv", "interpret"))
def fused_roi_attention_prequant(x: jax.Array,
                                 wq: jax.Array, sq_: jax.Array,
                                 wk: jax.Array, sk_: jax.Array,
                                 wv: jax.Array, sv_: jax.Array,
                                 key_mask: jax.Array | None = None, *,
                                 heads: int, kv_len: int | None = None,
                                 bits=8,
                                 bq: int = 128, bkv: int = 128,
                                 interpret: bool) -> jax.Array:
    """The serving hot path in one jit: int8 cached-weight QKV projections
    (``photonic_matmul_prequant`` x3 — the quantize-once cache's tuned MR
    banks) feeding the fused RoI-masked flash kernel.

    x (B, n, dm) float; wq/wk/wv (dm, dm) int8 codes with per-out-channel
    scales sq_/sk_/sv_ (dm,) f32; key_mask (B, n) keep-mask or None;
    ``kv_len`` the packed static alternative (one-shape serving mode).
    ``bits`` is an int or a static (q, k, v) triple of per-projection
    widths — mixed-precision bit plans may cache the three banks at
    different widths; each projection quantizes its activations at its
    own weight's width, exactly what the composed per-``linear`` dispatch
    does (the flash score core downstream is width-agnostic float).
    Returns the merged head outputs (B, n, dm) in x.dtype — the output
    projection is the caller's ``linear`` (it is just one more cached
    weight). Numerically identical to composing ``linear`` projections
    with ``attend`` under the flash backend; this entry point only removes
    the per-projection dispatch from the per-frame step graph.
    """
    if isinstance(bits, (tuple, list)):
        bits_q, bits_k, bits_v = (int(b_) for b_ in bits)
    else:
        bits_q = bits_k = bits_v = int(bits)
    b, n, dm = x.shape
    dh = dm // heads
    xf = x.astype(jnp.float32)
    q = photonic_matmul_prequant(xf, wq, sq_, bits=bits_q, interpret=interpret)
    k = photonic_matmul_prequant(xf, wk, sk_, bits=bits_k, interpret=interpret)
    v = photonic_matmul_prequant(xf, wv, sv_, bits=bits_v, interpret=interpret)

    def split(t):
        # cast to x.dtype first: bit-identical to the composed path, where
        # ``linear`` hands the attention core x.dtype operands
        return t.astype(x.dtype).reshape(b, n, heads, dh).transpose(0, 2, 1, 3)

    o = fused_masked_attention(split(q), split(k), split(v), key_mask,
                               kv_len=kv_len, bq=bq, bkv=bkv,
                               interpret=interpret)
    return o.transpose(0, 2, 1, 3).reshape(b, n, dm)


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = True) -> jax.Array:
    """models/attention.py layout: q (B, Sq, H, D); k/v (B, Skv, Hkv, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    sq, skv = qt.shape[2], kt.shape[2]
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          bq=bq, bkv=bkv, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
