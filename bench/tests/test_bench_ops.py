"""``bench/ops.py``, the ``opto_vit`` model's counts and ``bench/peaks.py``:
counts at known shapes, no padding or recompute counted, unknown chips
refused."""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import ops, peaks  # noqa: E402
from bench.models import opto_vit  # noqa: E402

BASE = json.loads((ROOT / "bench/configs/opto-vit-base-224.json").read_text())
LARGE = json.loads(
    (ROOT / "bench/configs/opto-vit-large-224.json").read_text())


def test_int8_matmul_ops_and_bytes():
    t = ops.matmul_int8(4, 8, 16)
    assert t.int8_ops == 2 * 4 * 8 * 16
    assert t.float_ops == 0
    # int8 x and w once, float32 result and per-channel scales once
    assert t.bytes == 4 * 8 + 8 * 16 + 4 * (4 * 16 + 16)


# by hand: per encoder layer 4 projections 8*t*d^2, attention 4*t^2*d,
# FFN 4*t*d*dff; the patch embed 2*196*768*d; MGNet about 0.28 GOP
@pytest.mark.parametrize("cfg,kept,gop", [
    (BASE, 98, 17.69),        # ViT-B/16 at 99 tokens: 12 x 1.431 GOP
    (LARGE, 196, 123.4),      # ViT-L/16 at 197 tokens: 24 x 5.116 GOP
])
def test_frame_ops_at_known_shapes(cfg, kept, gop):
    tot = opto_vit.frame_work(cfg, 1, 1, [(kept, 1)]).total()
    assert (tot.int8_ops + tot.float_ops) / 1e9 == pytest.approx(gop,
                                                                 rel=0.005)


def test_encoder_counts_by_kernel_base():
    d, dff, L = 768, 3072, 12
    t = 99                                   # 98 kept patches + [cls]
    w = opto_vit.encode_work(BASE, 98, 1)
    pm = w.by_kernel["photonic_matmul"]
    assert pm.int8_ops == L * 4 * 2 * t * d * d + 2 * d * 1000
    assert w.by_kernel["flash_attention_masked"].float_ops == \
        L * 2 * 2 * t * t * d
    assert w.by_kernel["fused_ffn"].int8_ops == L * 2 * 2 * t * d * dff


def test_only_real_rows_count():
    """A flush of 4 slots with 3 real frames needs exactly three frames'
    work: the padding frame adds nothing."""
    one = opto_vit.encode_work(BASE, 98, 1)
    three = opto_vit.encode_work(BASE, 98, 3)
    for k in opto_vit.KERNELS:
        assert three.by_kernel[k].int8_ops == pytest.approx(
            3 * one.by_kernel[k].int8_ops)
        assert three.by_kernel[k].float_ops == pytest.approx(
            3 * one.by_kernel[k].float_ops)


def test_ffn_counts_each_matmul_once():
    """The fused kernel recomputes x @ w1 per output tile; the count is
    the two matmuls of the layer's mathematics, once each."""
    d, dff, L, t = 768, 3072, 12, 99
    ffn = opto_vit.encode_work(BASE, 98, 1).by_kernel["fused_ffn"]
    once = 2 * t * d * dff + 2 * t * dff * d
    assert ffn.int8_ops == L * once


def test_roofline_bound_is_the_larger_of_compute_and_memory():
    p = peaks.peaks_for("TPU v5 lite")
    compute = ops.Tally(int8_ops=393e12)              # 1 s of int8
    assert compute.seconds_at_peak(p) == pytest.approx(1.0)
    memory = ops.Tally(int8_ops=1.0, bytes=819e9)     # 1 s of HBM
    assert memory.seconds_at_peak(p) == pytest.approx(1.0)
    mixed = ops.Tally(int8_ops=393e12, float_ops=197e12)
    assert mixed.compute_seconds_at_peak(p) == pytest.approx(2.0)


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.int8_ops_per_s, p.bf16_flops_per_s, p.hbm_bytes_per_s) == (
        393e12, 197e12, 819e9)
    assert "v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    with pytest.raises(ValueError):
        p.ops_per_s("fp8")
