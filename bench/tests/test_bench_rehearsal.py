"""CPU rehearsal of the benchmark: every cell resolves by name, each
traffic loop runs in-process at Tiny width, the result line has the
contract's keys, a non-TPU device is refused, and a new cell is data."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import loads, run  # noqa: E402
from bench.tests.tiny import (LADDER, overrides, patch_count,  # noqa: E402
                              rungs, tiny_patches)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = run.load_cell(cell)
    assert c["chips"] in (1, 4)
    assert c["cell"]["keep_patches"] <= patch_count(c["cfg"])
    assert set(c["cell"]["limits"]) == {"max_gap", "mean_gap"}
    for m in c["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    # a kernel's roofline names a kernel the cell's model declares
    for m in c["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["name"][:-len("_roofline")] in c["model"].KERNELS
    # the Tiny keep is a rung of the ladder over the model's Tiny patches,
    # all of them exactly where the cell keeps all of its own
    n_tiny = tiny_patches(cell)
    tiny = overrides(cell)["cell"]["keep_patches"]
    assert tiny in rungs(n_tiny, c["cell"]["server"].get("bucket_fractions",
                                                         LADDER))
    assert (tiny == n_tiny) == (c["cell"]["keep_patches"]
                                == patch_count(c["cfg"]))


def test_every_config_and_traffic_file_is_used_and_valid():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        loads.Traffic.load(w["traffic"], ROOT / "bench")


def _run_tiny(cell: str, seed: int, seconds: float, capsys
              ) -> tuple[dict, str]:
    """The cell at Tiny width on the CPU: its result and its stderr. A
    cell on more chips than this process sees runs in a child process
    with as many virtual CPU devices, so that its mesh is the cell's
    own."""
    import jax
    chips = run.load_cell(cell)["chips"]
    if jax.device_count() >= chips:
        res = run.run_cell(cell, seed, seconds, False,
                           overrides=overrides(cell), require_tpu=False)
        return res, capsys.readouterr().err
    code = (f"import json, sys; sys.path[:0] = [{str(ROOT)!r}, "
            f"{str(ROOT / 'src')!r}]\n"
            f"from bench import run\n"
            f"from bench.tests.tiny import overrides\n"
            f"print(json.dumps(run.run_cell({cell!r}, {seed}, {seconds}, "
            f"False, overrides=overrides({cell!r}), require_tpu=False)))")
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count={chips}").strip())
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_loop_runs_in_process(cell, capsys):
    res, err = _run_tiny(cell, 2**31 + 12345, 1.0, capsys)
    # set-up warmed every shape the window uses
    assert "tracing and compiling inside the window: 0 events" in err
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert set(res) == set(KEYS) | {"checks"}
    c = run.load_cell(cell)
    assert res["device"]["count"] == c["chips"]
    assert set(res["metrics"]) == {m["name"] for m in c["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["frames_missing"]["value"] == 0
    assert res["checks"]["flushes_off_bucket"]["value"] == 0
    json.dumps(res)


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    # a trace directory of its own: tests in other workers trace too
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    cell = "base224-keep33"
    res = run.run_cell(cell, 7, 1.0, True, overrides=overrides(cell),
                       require_tpu=False)
    m = res["metrics"]
    assert m["flush_fill"]["value"] == 4.0
    assert 0 < m["mgnet_score_share"]["value"] <= 100
    assert 0 <= m["device_idle_share"]["value"] < 100
    # no peaks for a CPU: no share of a peak or a roofline is reported
    assert not any(k.endswith("roofline") or "mfu" in k for k in m)
    d = res["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_cli_refuses_a_non_tpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_a_new_cell_is_data_only(tmp_path):
    """A cell added as BENCHMARK.json entry + traffic file + cell file runs
    with no edit to any code."""
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench/configs")
    shutil.copytree(ROOT / "bench" / "models", tmp_path / "bench/models",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "bench" / "workloads",
                    tmp_path / "bench/workloads")
    shutil.copytree(ROOT / "bench" / "traffic", tmp_path / "bench/traffic")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "base224-keep50-cams4",
                              "config": "opto-vit-base-224",
                              "traffic": "closed4", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "base224-keep33" in m.get("workloads", []):
            m["workloads"].append("base224-keep50-cams4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    t = json.loads((ROOT / "bench/traffic/closed16.json").read_text())
    t["cameras"] = 4
    (tmp_path / "bench/traffic/closed4.json").write_text(json.dumps(t))
    cellf = json.loads(
        (ROOT / "bench/workloads/base224-keep33.json").read_text())
    cellf["server"]["force_bucket"] = 0.5
    (tmp_path / "bench/workloads/base224-keep50-cams4.json").write_text(
        json.dumps(cellf))
    ov = overrides("base224-keep50-cams4", root=tmp_path)
    assert ov["cell"]["keep_patches"] == 8   # 0.5 of 16 patches
    ov["traffic"]["cameras"] = 3
    res = run.run_cell("base224-keep50-cams4", 3, 0.5, False,
                       root=tmp_path, overrides=ov, require_tpu=False)
    assert res["checks"]["flushes_off_bucket"]["value"] == 0
    assert "frames_per_s" in res["metrics"]


def test_serve_mfu_reads_the_untraced_window():
    """The step's share of the peak is taken over the window served at the
    host's own pace, not over the traced stretch after it."""
    from bench import ops, peaks
    c = run.load_cell("base224-keep33")
    work = run._window_work(c["model"], c["cfg"], loads.Served(
        frames=512, scored=64, flushes=[(98, 4)] * 128))
    pk = peaks.peaks_for("TPU v5 lite")
    ctx = {"peaks": pk, "window_work": work, "chips": 1,
           "served": loads.Served(window_s=2.0),
           "traced": loads.Served(window_s=10.0),
           "work": ops.Work()}
    need = work.total().compute_seconds_at_peak(pk)
    assert run._reader("serve_mfu")(ctx) == pytest.approx(100 * need / 2.0)
    assert run._reader("serve_mfu")(dict(ctx, peaks=None)) is None


def test_arrival_times_are_the_same_for_every_seed():
    t = loads.Traffic.load("clips32-open", ROOT / "bench")
    due1, cams1 = loads.arrivals(t, 20.0, 1)
    due2, cams2 = loads.arrivals(t, 20.0, 2**31 + 9)
    assert len(due1) == round(t.clips_per_s * 20.0)
    assert (due1 == due2).all() and (cams1 != cams2).any()
    assert sorted(cams1) == sorted(cams2)
    assert abs(due1[-1] - 20.0) < 2.0        # the rate holds over the window
