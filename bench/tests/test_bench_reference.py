"""``bench/reference.py`` with the ``opto_vit`` model against what
``StreamServer`` served, at Tiny width on the CPU, and its restatement of
the mask-cache rule."""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import reference, run  # noqa: E402
from bench.models import opto_vit  # noqa: E402
from bench.tests.tiny import overrides  # noqa: E402

FLOAT = {"quant_bits": 0,
         "backends": {"matmul": "bf16", "attn": "xla", "ffn": "xla"}}


@pytest.mark.parametrize("cell", ["base224-keep33", "base224-clips-open"])
def test_reference_matches_the_served_float32_path(cell):
    """With the program on its float32 path (its ``bf16`` matmul backend
    keeps float32 operands and accumulates in float32 on the CPU) every
    served class is the reference's best: the two differ only in the
    order of float32 sums, far below any logit gap that could reorder
    classes. Covers the patch embed, MGNet with the mask cache's reuse,
    the top-k gather, every encoder layer and the head."""
    res = run.run_cell(cell, 2**31 + 5, 0.5, False,
                       overrides=overrides(cell, **FLOAT),
                       require_tpu=False)
    assert res["checks"]["max_gap"]["value"] <= 1e-4


def test_reference_is_blocked_and_padded_the_same():
    cfg = json.loads((ROOT / "bench/configs/opto-vit-base-224.json")
                     .read_text())
    cfg.update(overrides("base224-keep33")["config"])
    params = reference.make_init(opto_vit, cfg, 3)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 64, 64, 3)).astype(np.float32)
    s = rng.normal(size=(5, 64, 64, 3)).astype(np.float32)
    a = reference.ReferenceModel(opto_vit, params, cfg, keep=8,
                                 block=2).logits(x, s)
    b = reference.ReferenceModel(opto_vit, params, cfg, keep=8,
                                 block=8).logits(x, s)
    assert a.shape == (5, 1000)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_weights_follow_the_seed():
    cfg = json.loads((ROOT / "bench/configs/opto-vit-base-224.json")
                     .read_text())
    cfg.update(overrides("base224-keep33")["config"])
    a = reference.make_init(opto_vit, cfg, 2**33 + 1)
    b = reference.make_init(opto_vit, cfg, 2**33 + 1)
    c = reference.make_init(opto_vit, cfg, 2**33 + 2)
    np.testing.assert_array_equal(a["head"], b["head"])
    assert not np.array_equal(a["head"], c["head"])


def test_scoring_frames_rule():
    f = np.zeros((12, 4, 4, 3), np.float32)
    f[5] += 1.0                     # a cut: far from frame 0
    f[6] += 1.0
    got = reference.scoring_frames(f, refresh=4, threshold=0.15)
    # 0 scored; 1..3 reuse it; 4 is 4 frames on (refresh); 5 differs by
    # 1.0 > 0.15; 6 matches 5; 7 differs from 5; 8..10 reuse 7; 11 refresh
    assert got.tolist() == [0, 0, 0, 0, 4, 5, 5, 7, 7, 7, 7, 11]


def test_served_gaps():
    ref = np.array([[0.0, 1.0, 2.0, 3.0], [3.0, 2.0, 1.0, 0.0]])
    sd = ref[0].std()
    np.testing.assert_allclose(reference.served_gaps(ref, [3, 1]),
                               [0.0, 1.0 / sd])
