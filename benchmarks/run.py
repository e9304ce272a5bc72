"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig8 table4

Each module's ``run()`` prints its table and ASSERTS the paper's
qualitative claims (orderings, dominances, calibrated headline) so the
harness doubles as a reproduction gate."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks import (attention_bench, bench_backend_cache,
                        controller_bench, fault_bench, ffn_bench,
                        fig8_energy, fig9_latency, fig10_11_mgnet,
                        fleet_bench, mixed_precision_bench,
                        multistream_bench, robustness_bench, roofline_table,
                        serving_bench, table1_qat, table4_kfps)

ALL = {
    "fig8": fig8_energy.run,
    "fig9": fig9_latency.run,
    "fig10_11": fig10_11_mgnet.run,
    "table1": table1_qat.run,
    "table4": table4_kfps.run,
    "roofline": roofline_table.run,
    "cache": bench_backend_cache.run,
    "serving": serving_bench.run,
    "attention": attention_bench.run,
    # the fused-FFN gate merges into BENCH_serving.json under "ffn" (same
    # pattern as attention_bench) so the perf trajectory stays in one file
    "ffn": ffn_bench.run,
    # multi-stream session server vs sequential cold engines ("multistream"
    # key in BENCH_serving.json)
    "multistream": multistream_bench.run,
    # per-layer bit plans on the fused path: speedup / energy / agreement
    # gates ("mixed_precision" key in BENCH_serving.json)
    "mixed_precision": mixed_precision_bench.run,
    # serving control plane: calibration medrelerr + autotune fps gates
    # ("controller" key in BENCH_serving.json)
    "controller": controller_bench.run,
    # clean-vs-noisy agreement, accuracy-under-drift, drift-triggered
    # recalibration ("robustness" key in BENCH_serving.json)
    "robustness": robustness_bench.run,
    # chaos gates: transient-fault bitwise transparency + fps floor,
    # per-session quarantine isolation, crash-and-restore exactness
    # ("faults" key in BENCH_serving.json)
    "faults": fault_bench.run,
    # fleet front-end: 1 -> W worker scaling, cost-vs-rr placement, and
    # model-sharded fused-encode bitwise parity on a forced 4-device host
    # ("fleet" key in BENCH_serving.json)
    "fleet": fleet_bench.run,
}

HISTORY = os.environ.get("BENCH_HISTORY_JSONL", "BENCH_history.jsonl")
HISTORY_KEEP = 200


def _git_sha() -> str | None:
    """Short HEAD SHA, or None outside a repo / without git."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _append_history(names, failed, dt: float) -> None:
    """One JSONL row per harness run: when, which commit, what ran, what
    failed, and the merged BENCH_serving.json snapshot — the perf
    trajectory over PRs. The file is rotated to the newest HISTORY_KEEP
    rows so a long-lived checkout's log stays bounded."""
    snapshot = None
    if os.path.exists(mixed_precision_bench.OUT_JSON):
        try:
            with open(mixed_precision_bench.OUT_JSON) as f:
                snapshot = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    row = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
           "sha": _git_sha(),
           "names": list(names), "failed": [n for n, _ in failed],
           "elapsed_s": round(dt, 1), "serving": snapshot}
    rows = []
    if os.path.exists(HISTORY):
        with open(HISTORY) as f:
            rows = [ln for ln in f.read().splitlines() if ln.strip()]
    rows.append(json.dumps(row))
    with open(HISTORY, "w") as f:
        f.write("\n".join(rows[-HISTORY_KEEP:]) + "\n")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or list(ALL)
    t0 = time.time()
    failed = []
    for n in names:
        try:
            ALL[n]()
        except AssertionError as e:
            failed.append((n, str(e)))
            print(f"!! {n} reproduction assertion failed: {e}")
    dt = time.time() - t0
    _append_history(names, failed, dt)
    print(f"\n== benchmarks done in {dt:.1f}s: "
          f"{len(names) - len(failed)}/{len(names)} reproduction gates pass")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
