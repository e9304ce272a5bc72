"""Find the open-loop knee of a cell once: the highest clip rate at which
the backlog does not grow.

    python bench/sweep.py --workload base224-clips-open --seed 1 \\
        --seconds 8 --rates 20,40,60,80

Set-up runs once; then one open-loop window per rate, each printed as a
JSON line: clips, latency quantiles, how long the last clip finished
after the window's last arrival (``drain_s``), and the median latency of
the last fifth of the clips against the first fifth (a backlog that
grows shows as a ratio well above 1). The open cell's traffic file
fixes its rate below the knee found here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import loads, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    st = run.setup_cell(c, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        t = dataclasses.replace(c["traffic"], clips_per_s=rate)
        due, _ = loads.arrivals(t, args.seconds, args.seed)
        s = loads.open_loop(st["server"], st["streams"], t, args.seconds,
                            args.seed)
        lat = s.clip_latency_s
        fifth = max(1, len(lat) // 5)
        print(json.dumps({
            "clips_per_s": rate, "clips": len(lat),
            "frames_per_s": s.frames / s.window_s,
            "p50_ms": 1e3 * statistics.median(lat),
            "p95_ms": 1e3 * run._p95(lat),
            "drain_s": s.window_s - float(due[-1]),
            "last_over_first": (statistics.median(lat[-fifth:])
                                / statistics.median(lat[:fifth])),
            "calls": s.calls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
