"""The check that decides ``correct`` fails what it must, at Tiny width on
the CPU with each cell's own limits: the program's own int4 path (the
control: one precision below the configuration's int8), a class altered
where the encoder produces it, and frames a flush drops. The chip
readings these limits were set from are in PERF.md."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from bench import run  # noqa: E402
from bench.tests.tiny import overrides  # noqa: E402

CLOSED, OPEN = "base224-keep33", "base224-clips-open"
SEED = 2**31 + 77


def _run(cell, hook=None, **config):
    return run.run_cell(cell, SEED, 0.5, False,
                        overrides=overrides(cell, **config),
                        require_tpu=False, server_hook=hook)


def test_int4_control_is_not_correct_and_int8_reads_far_below_it():
    sound = _run(CLOSED)["checks"]
    control = _run(CLOSED, quant_bits=4)["checks"]
    assert control["mean_gap"]["value"] > control["mean_gap"]["limit"]
    assert control["mean_gap"]["value"] > 3 * sound["mean_gap"]["value"]


def _altered_class(server):
    """The encoder's logits come out rolled by one class."""
    enc = server._encode
    server._encode = lambda p, t, *a: jnp.roll(enc(p, t, *a), 1, axis=-1)


def _dropped_frames(server):
    """Each flush predicts only its first half of real frames."""
    finish = server._finish

    def half(fb, by_sid):
        keep = max(1, fb.n_real // 2)
        fb.frame_idx = fb.frame_idx[:keep]
        fb.n_real = keep
        return finish(fb, by_sid)

    server._finish = half


@pytest.mark.parametrize("cell", [CLOSED, OPEN])
def test_an_altered_class_is_not_correct(cell):
    res = _run(cell, _altered_class)
    assert res["correct"] is False
    g = res["checks"]["mean_gap"]
    assert g["value"] > g["limit"]


@pytest.mark.parametrize("cell", [CLOSED, OPEN])
def test_dropped_frames_are_not_correct(cell):
    res = _run(cell, _dropped_frames)
    assert res["correct"] is False
    assert res["checks"]["frames_missing"]["value"] > 0
    assert res["failed"] > 0


def test_a_frame_answered_by_another_chip_is_not_correct():
    """The four-chip cell, on four virtual CPU devices in a child process:
    every frame of a flush takes the answer of the frame on the first
    chip, as if the other chips' answers never came back."""
    import json
    import os
    import subprocess
    cell = "base224-keep33-dp4"
    chips = run.load_cell(cell)["chips"]
    code = f"""
import json, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
import jax.numpy as jnp
from bench import run
from bench.tests.tiny import overrides

def first_chip(server):
    enc = server._encode
    def one(p, t, *a):
        out = enc(p, t, *a)
        return jnp.broadcast_to(out[:1], out.shape)
    server._encode = one

res = run.run_cell({cell!r}, {SEED}, 0.5, False, overrides=overrides({cell!r}),
                   require_tpu=False, server_hook=first_chip)
print(json.dumps(res))
"""
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count={chips}").strip())
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == chips
    assert res["correct"] is False
    g = res["checks"]["mean_gap"]
    assert g["value"] > g["limit"]
