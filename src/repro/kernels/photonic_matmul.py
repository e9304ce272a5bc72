"""Pallas TPU kernel: photonic-constrained w8a8 integer MatMul.

TPU adaptation of the Opto-ViT optical core (DESIGN.md §2). The optical
core multiplies a 32-element input chunk (wavelength channels) against a
32x64 MR weight tile per cycle and accumulates chunk partials
electronically (paper Fig. 6). On TPU the analogous schedule is a blocked
int8 x int8 -> int32 MXU matmul whose K-grid walk plays the role of the
wavelength-chunk walk:

  * block shapes are multiples of the photonic (32, 64) tile, aligned up
    to the MXU native 128 lane width: bm x bk x bn = 128 x 128 x 128
    (one K-block = 4 wavelength chunks; one N-block = 2 arm groups),
  * accumulation is int32 in VMEM scratch across the K grid dimension
    (the electronic partial-sum accumulate),
  * the dequant epilogue applies the per-tensor activation scale and
    per-output-channel weight scale on the last K step (the ADC + scale
    restore), writing f32.

Numerics contract: the integer accumulate matches kernels/ref.py::
photonic_matmul_ref exactly; the f32 dequant epilogue may differ by XLA
reassociation (<= 2 ulp). Validated under interpret=True on CPU for
shape/dtype sweeps in tests/test_kernels_photonic.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["photonic_matmul_kernel", "photonic_matmul_int8"]

# photonic tile geometry (paper Fig. 3b): 32 wavelengths x 64 arms
WAVELENGTHS = 32
ARMS = 64


def photonic_matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref):
    """Grid (M/bm, N/bn, K/bk). x int8 (bm,bk); w int8 (bk,bn);
    sx (1,1) f32; sw (1,bn) f32; o f32 (bm,bn); acc int32 scratch."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 (MXU integer path). The K-block walk is the
    # wavelength-chunk accumulate of paper Fig. 6.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        # dequant: per-tensor activation scale x per-channel weight scale.
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[0, 0] * sw_ref[0, :][None, :])


def photonic_matmul_int8(xq: jax.Array, wq: jax.Array, sx: jax.Array,
                         sw: jax.Array, *, bm: int = 128, bn: int = 128,
                         bk: int = 128, interpret: bool = True) -> jax.Array:
    """xq (M,K) int8, wq (K,N) int8, sx () f32, sw (N,) f32 -> (M,N) f32.

    M/K/N must be multiples of the block sizes (callers pad; ops.py does).
    ``interpret=True`` executes the kernel body in Python on CPU — the
    validation mode for this host; on a real TPU pass interpret=False.
    """
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (xq.shape, wq.shape, bm, bn, bk)
    assert bk % WAVELENGTHS == 0 and bn % ARMS == 0, (bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        photonic_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            pl.BlockSpec((1, 1), lambda i, j, l: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="photonic_matmul",
    )(xq, wq, sx.reshape(1, 1), sw.reshape(1, n))
