"""Readings for a cell's correctness limits, on the chip.

    python bench/control.py --workload base224-keep33 --seeds 1,2,3 \\
        --seconds 3 [--bits 4]

Runs the cell once per seed in one process (set-up, a short window at
the cell's own load, the check) and prints each run's compared numbers
as a JSON line. Without ``--bits`` it reads the program as the
configuration states it (the lower readings); ``--bits 4`` switches on
the program's own int4 path, one precision below the configuration's
int8 (the control, the upper readings). The benchmark's runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--bits", type=int, default=0)
    args = ap.parse_args(argv)
    ov = {"config": {"quant_bits": args.bits}} if args.bits else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           overrides=ov)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "bits": args.bits or None,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
