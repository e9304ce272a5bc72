"""Pallas flash-attention kernels vs the shared dense oracle
(kernels/ref.py::flash_attention_ref, interpret mode) — causal/local and
the RoI-masked serving variant. Generated-shape coverage of the masked
kernel lives in tests/test_differential.py; these are the pinned cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_masked,
                                           flash_attention_masked_xla)
from repro.kernels.ops import fused_attention
from repro.kernels.ref import flash_attention_ref

pytestmark = pytest.mark.slow      # interpret-mode kernels -> CI slow job


def _qkv(key, b, h, hkv, sq, skv, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, h, sq, d), dtype)
    k = jax.random.normal(k2, (b, hkv, skv, d), dtype)
    v = jax.random.normal(k3, (b, hkv, skv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 4, 4, 128, 32),       # MHA
    (2, 4, 2, 128, 32),       # GQA 2x
    (1, 8, 1, 256, 16),       # MQA
])
def test_causal_matches_ref(b, h, hkv, s, d):
    q, k, v = _qkv(jax.random.PRNGKey(0), b, h, hkv, s, s, d)
    out = flash_attention(q, k, v, causal=True, bq=64, bkv=64)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 64])
def test_local_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, 2, 128, 128, 16)
    out = flash_attention(q, k, v, causal=True, window=window, bq=32,
                          bkv=32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_non_causal():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, 64, 128, 32)
    out = flash_attention(q, k, v, causal=False, bq=32, bkv=64)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bkv", [(32, 32), (64, 128), (128, 64)])
def test_block_shape_invariance(bq, bkv):
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 4, 2, 128, 128, 32)
    out = flash_attention(q, k, v, causal=True, bq=bq, bkv=bkv)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_io():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 2, 2, 64, 64, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, bq=32, bkv=32)
    ref = flash_attention_ref(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------------------
# RoI-masked variant (key keep-mask / packed kept-count)
# --------------------------------------------------------------------------

def _masked_setup(seed=0, b=2, h=4, s=37, d=32, density=0.5):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k1, (b, h, s, d))
    k = jax.random.normal(k2, (b, h, s, d))
    v = jax.random.normal(k3, (b, h, s, d))
    mask = (jax.random.uniform(k4, (b, s)) < density
            ).astype(jnp.float32).at[:, 0].set(1.0)
    return q, k, v, mask


def test_masked_matches_ref():
    q, k, v, mask = _masked_setup()
    out = flash_attention_masked(q, k, v, mask, bq=16, bkv=16)
    ref = flash_attention_ref(q, k, v, causal=False, key_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bkv", [(8, 8), (16, 32), (64, 16), (128, 128)])
def test_masked_block_shape_invariance(bq, bkv):
    """Block tiling (and therefore which KV blocks get skipped) must not
    change the numbers — the streaming-softmax merge is exact."""
    q, k, v, mask = _masked_setup(seed=1, s=48, density=0.3)
    ref = flash_attention_ref(q, k, v, causal=False, key_mask=mask)
    out = flash_attention_masked(q, k, v, mask, bq=bq, bkv=bkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_masked_kernel_agrees_with_xla_lowering():
    """The two lowerings of fused_masked_attention (Pallas kernel vs the
    CPU-host XLA twin) implement one contract."""
    q, k, v, mask = _masked_setup(seed=2, s=24, density=0.4)
    a = flash_attention_masked(q, k, v, mask, bq=8, bkv=8)
    b = flash_attention_masked_xla(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_masked_kvlen_packed_skip():
    """Packed kept-count: keys >= kv_len contribute nothing, and changing
    their values must not change the output (they are never computed)."""
    q, k, v, _ = _masked_setup(seed=3, s=32)
    out = flash_attention_masked(q, k, v, kv_len=9, bq=8, bkv=8)
    # poison the dead tail: a skipped block must never read it
    k_poison = k.at[:, :, 16:].set(1e4)
    v_poison = v.at[:, :, 16:].set(-1e4)
    out_p = flash_attention_masked(q, k_poison, v_poison, kv_len=9,
                                   bq=8, bkv=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))
    prefix = jnp.broadcast_to((jnp.arange(32) < 9).astype(jnp.float32)[None],
                              (2, 32))
    ref = flash_attention_ref(q, k, v, causal=False, key_mask=prefix)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_roi_attention_prequant_matches_float_composition():
    """ops.fused_roi_attention_prequant (int8 cached projections + fused
    masked attention) == quantize-dequant projections + the dense oracle,
    to f32 epilogue noise."""
    from repro.core.backend import quantize_weight
    from repro.kernels.ops import fused_roi_attention_prequant

    b, n, dm, heads = 2, 17, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, n, dm))
    ws = [jax.random.normal(kk, (dm, dm)) for kk in ks[1:4]]
    mask = (jax.random.uniform(ks[4], (b, n)) < 0.6
            ).astype(jnp.float32).at[:, 0].set(1.0)
    qws = [quantize_weight(w) for w in ws]
    out = fused_roi_attention_prequant(
        x, qws[0].wq, qws[0].scale.reshape(-1),
        qws[1].wq, qws[1].scale.reshape(-1),
        qws[2].wq, qws[2].scale.reshape(-1), mask, heads=heads,
        interpret=True)

    from repro.core.backend import ExecPolicy, linear
    pol = ExecPolicy(backend="photonic_pallas", quant_bits=8)
    proj = [linear(x, qw, policy=pol) for qw in qws]
    split = [p.reshape(b, n, heads, dm // heads).transpose(0, 2, 1, 3)
             for p in proj]
    ref = flash_attention_ref(*split, causal=False, key_mask=mask)
    ref = ref.transpose(0, 2, 1, 3).reshape(b, n, dm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_attention_models_layout():
    """(B, S, H, D) wrapper == models/attention layout oracle."""
    from repro.models.attention import full_attention
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, 128, 4, 32))
    k = jax.random.normal(k2, (2, 128, 2, 32))
    v = jax.random.normal(k3, (2, 128, 2, 32))
    out = fused_attention(q, k, v, causal=True, bq=64, bkv=64)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
