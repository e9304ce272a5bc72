"""Pallas TPU kernel: fused flash attention (GQA, causal/local).

Streaming-softmax attention with VMEM-resident running (max, sum, acc)
state — the (Sq, Skv) score matrix never reaches HBM. Grid layout:

    grid = (B * H, Sq/bq, Skv/bkv)

The innermost (KV) grid dimension accumulates into VMEM scratch; on the
last KV step the normalized block output is written. GQA is expressed in
the BlockSpec index maps: query row ``i`` reads KV row ``i // group`` —
no KV repetition materializes.

Causal + local-window masking is applied per element; fully-masked KV
blocks are skipped with ``pl.when`` (the kernel-level analogue of the
causal_block_skip hillclimb in the XLA path).

``flash_attention_masked`` is the RoI-aware variant the Opto-ViT serving
hot path runs: it takes a per-batch key keep-mask (or a packed kept-count
for the bucketed ladder), applies it inside the streaming-softmax update,
and skips KV blocks whose keys are *all* pruned — so non-RoI patches cost
zero score FLOPs instead of being masked after the full (Sq, Skv) compute
is paid. The per-(batch, kv-block) live counts are reduced once on the
XLA side and scalar-prefetched into SMEM, so the skip predicate is a
scalar read per grid step.

Validated in interpret mode against kernels/ref.py::flash_attention_ref
over shape/dtype/mask sweeps (tests/test_kernels_flash.py and the
hypothesis harness in tests/test_differential.py).

Bit widths: the score-softmax-PV core is float end to end (score and PV
products are activation-activation matmuls — dynamically tuned cores on
the photonic hardware), so this kernel is *width-agnostic*: under a
mixed-precision bit plan the per-projection widths live entirely in the
upstream int8 Q/K/V projections (kernels/ops.py::
fused_roi_attention_prequant quantizes each projection's activations at
its own cached weight's width); nothing here takes a ``bits`` parameter.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import expand_kv_heads, prefix_key_mask

__all__ = ["flash_attention_kernel", "flash_attention",
           "flash_attention_masked_kernel", "flash_attention_masked",
           "flash_attention_masked_xla", "fused_masked_attention"]

NEG_INF = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                           *, scale: float, causal: bool, window: int,
                           bq: int, bkv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    kv_lo = ki * bkv

    # live = this KV block intersects the visible region of this Q block
    live = True
    if causal:
        live = kv_lo <= q_lo + bq - 1
    if window > 0:
        live = jnp.logical_and(live, kv_lo + bkv - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= q_pos >= kv_pos
        if window > 0:
            mask &= q_pos - kv_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v_ref[0].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q (B, H, Sq, D); k/v (B, Hkv, Skv, D) -> (B, H, Sq, D).

    H must be a multiple of Hkv (GQA group = H // Hkv); Sq % bq == 0,
    Skv % bkv == 0. D should be a multiple of 128 on real TPUs (lane
    alignment); interpret mode accepts any D.
    """
    b, h, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0 and sq % bq == 0 and skv % bkv == 0, \
        (q.shape, k.shape, bq, bkv)
    g = h // hkv
    scale = 1.0 / math.sqrt(dh)
    qf = q.reshape(b * h, sq, dh)
    kf = k.reshape(b * hkv, skv, dh)
    vf = v.reshape(b * hkv, skv, dh)

    grid = (b * h, sq // bq, skv // bkv)
    kern = functools.partial(flash_attention_kernel, scale=scale,
                             causal=causal, window=window, bq=bq, bkv=bkv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bkv, dh), lambda i, qi, ki, g=g: (i // g, ki, 0)),
            pl.BlockSpec((1, bkv, dh), lambda i, qi, ki, g=g: (i // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, dh)


# --------------------------------------------------------------------------
# RoI-masked variant (key-axis keep-mask, fully-pruned KV blocks skipped)
# --------------------------------------------------------------------------

def flash_attention_masked_kernel(nlive_ref, q_ref, k_ref, v_ref, mask_ref,
                                  o_ref, m_ref, l_ref, acc_ref, *,
                                  scale: float, heads: int):
    """One (bq, bkv) tile of key-masked bidirectional flash attention.

    ``nlive_ref`` is the scalar-prefetched (B, nkv) table of unmasked-key
    counts per KV block; when this block's count is zero the whole tile is
    skipped — no score dot, no softmax update, no PV dot. Inside a live
    tile masked keys get ``NEG_INF`` scores so they carry exactly-zero
    probability weight. A live tile has >= 1 unmasked key, so every row
    max stays finite and the classic ``exp(NEG_INF - NEG_INF)`` poisoning
    cannot occur.
    """
    ki = pl.program_id(2)
    nlive = nlive_ref[pl.program_id(0) // heads, ki]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(nlive > 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask_ref[0] > 0, s, NEG_INF)        # (1, bkv) bcast

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v_ref[0].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        # rows whose every key is masked (l == 0) output exactly zero
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _fit_block(s: int, block: int) -> int:
    """Largest usable block size for a length-``s`` axis: ``block`` when the
    axis exceeds it, else the axis rounded up to the f32 sublane (8)."""
    return block if s > block else -(-s // 8) * 8


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    r = (-x.shape[axis]) % to
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


def flash_attention_masked(q: jax.Array, k: jax.Array, v: jax.Array,
                           key_mask: jax.Array | None = None, *,
                           kv_len: jax.Array | int | None = None,
                           scale: float | None = None,
                           bq: int = 128, bkv: int = 128,
                           interpret: bool = True) -> jax.Array:
    """Key-masked bidirectional flash attention (the RoI serving kernel).

    q (B, H, Sq, D); k (B, Hk, Skv, D); v (B, Hv, Skv, Dv) ->
    (B, H, Sq, Dv). H must be a multiple of both Hk and Hv (independent
    GQA groups, so the Eq. 2 decomposed dataflow — shared X as keys,
    per-head V — routes through the same kernel). D and Dv may differ.

    ``key_mask`` (B, Skv) keep-mask ({0,1}, any numeric dtype) prunes keys
    per batch row; ``kv_len`` (scalar or (B,)) is the packed alternative
    for the bucketed path — key j is kept iff j < kv_len. Give at most
    one. KV blocks with no kept key are skipped inside the kernel
    (``pl.when`` on a scalar-prefetched live count), so a 50%-pruned
    packed stream pays ~50% of the score/PV FLOPs. ``scale`` defaults to
    1/sqrt(D); pass 1.0 when the scale is already folded into Q (Eq. 2).

    Sq/Skv need not be block multiples: both are padded (padded keys are
    masked out, padded query rows sliced off). Rows with zero live keys
    return exactly 0 — matching kernels/ref.py::flash_attention_ref.
    """
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    _, hv, _, dv = v.shape
    assert h % hk == 0 and h % hv == 0, (q.shape, k.shape, v.shape)
    assert k.shape[2] == v.shape[2], (k.shape, v.shape)
    if key_mask is not None and kv_len is not None:
        raise ValueError("give key_mask or kv_len, not both")
    if key_mask is None:
        key_mask = (jnp.ones((b, skv), jnp.float32) if kv_len is None
                    else prefix_key_mask(kv_len, b, skv))
    assert key_mask.shape == (b, skv), (key_mask.shape, (b, skv))
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    bq = _fit_block(sq, bq)
    bkv = _fit_block(skv, bkv)
    qp = _pad_axis(q, 2, bq)
    kp = _pad_axis(k, 2, bkv)
    vp = _pad_axis(v, 2, bkv)
    maskp = _pad_axis(key_mask.astype(jnp.float32), 1, bkv)
    sqp, skvp = qp.shape[2], kp.shape[2]
    nkv = skvp // bkv
    # per-(batch, kv-block) live-key counts — the block-skip predicate,
    # scalar-prefetched into SMEM whole (a per-step (1, 1) block of a
    # (B, nkv) array breaks the TPU's (8, 128) block tiling rule)
    nlive = maskp.reshape(b, nkv, bkv).sum(-1).astype(jnp.int32)
    # the mask gets a unit middle axis so its (1, bkv) block trails with
    # dims the tiling rule accepts (1 = full extent, bkv = 128 or full)
    mask3 = maskp.reshape(b, 1, skvp)

    gk, gv = h // hk, h // hv
    qf = qp.reshape(b * h, sqp, d)
    kf = kp.reshape(b * hk, skvp, d)
    vf = vp.reshape(b * hv, skvp, dv)

    kern = functools.partial(flash_attention_masked_kernel, scale=scale,
                             heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, sqp // bq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, qi, ki, nl: (i, qi, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda i, qi, ki, nl, gk=gk: (i // gk, ki, 0)),
            pl.BlockSpec((1, bkv, dv),
                         lambda i, qi, ki, nl, gv=gv: (i // gv, ki, 0)),
            pl.BlockSpec((1, 1, bkv),
                         lambda i, qi, ki, nl, h=h: (i // h, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv),
                               lambda i, qi, ki, nl: (i, qi, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, dv), q.dtype),
        interpret=interpret,
        name="flash_attention_masked",
    )(nlive, qf, kf, vf, mask3)
    return out.reshape(b, h, sqp, dv)[:, :, :sq]


def flash_attention_masked_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                               key_mask: jax.Array | None = None, *,
                               kv_len: jax.Array | int | None = None,
                               scale: float | None = None) -> jax.Array:
    """XLA lowering of ``flash_attention_masked`` (same shapes/semantics).

    On CPU hosts the Pallas interpreter is a correctness emulator, not a
    perf path (same policy as models/attention.py), so the "flash"
    attention backend lowers here instead. The kernel's block-skip shows
    up as **static packed skip**: a Python-int ``kv_len`` (the bucketed
    serving path — ladder sizes are static by construction) slices the
    dead KV tail away before any score FLOP is spent, the XLA analogue of
    ``pl.when`` skipping fully-pruned KV blocks — at sublane (8)
    granularity, since XLA has no MXU tile constraint. Scattered array
    masks keep the full key set under an additive bias — the same cost as
    the "xla" backend (the per-block skip win for those needs the real
    TPU kernel) — plus the kernel's exact-zero guard for batch rows whose
    every key is pruned.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if key_mask is not None and kv_len is not None:
        raise ValueError("give key_mask or kv_len, not both")
    if kv_len is not None and not hasattr(kv_len, "shape"):
        # static kept-count: drop the dead KV tail before the compute
        lim = min(skv, max(8, -(-int(kv_len) // 8) * 8))
        k, v = k[:, :, :lim], v[:, :, :lim]
        skv = lim
        key_mask = prefix_key_mask(int(kv_len), b, lim)
    elif kv_len is not None:
        key_mask = prefix_key_mask(kv_len, b, skv)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qf = q.astype(jnp.float32) * scale
    s = qf @ jnp.swapaxes(expand_kv_heads(k, h).astype(jnp.float32), -1, -2)
    if key_mask is not None:
        s = s + ((key_mask.astype(jnp.float32) - 1.0)
                 * -NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = p @ expand_kv_heads(v, h).astype(jnp.float32)
    if key_mask is not None:
        # batch rows with zero live keys output exactly 0 (kernel contract)
        o = o * (key_mask.sum(-1) > 0)[:, None, None, None]
    return o.astype(q.dtype)


def fused_masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           key_mask: jax.Array | None = None, *,
                           kv_len: jax.Array | int | None = None,
                           scale: float | None = None,
                           bq: int = 128, bkv: int = 128,
                           interpret: bool) -> jax.Array:
    """The RoI-masked attention core, lowered for the host it runs on:
    the Pallas kernel when compiling for TPU (``interpret=False``), the
    XLA twin on CPU hosts. Both implement the identical contract
    (tests/test_differential.py pins them against each other)."""
    if interpret:
        return flash_attention_masked_xla(q, k, v, key_mask, kv_len=kv_len,
                                          scale=scale)
    return flash_attention_masked(q, k, v, key_mask, kv_len=kv_len,
                                  scale=scale, bq=bq, bkv=bkv,
                                  interpret=False)
