#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, on the chip, in one process.

    python3 bench/control.py --workload mixtral_8x7b.oneshot \\
        --seeds 101,102,... --control-seeds 201,202,203 --seconds 3

For each ``--seeds`` seed, one run of the cell as the benchmark makes it
(set-up, a short window at the cell's own load, the comparison): the
program's readings. For each ``--control-seeds`` seed, the same run twice
with a reference one precision lower in the program's place: the J/op
program in bfloat16, and the WS partial sums accumulated in float32. Each
run prints one JSON line with its compared numbers; the limits in
``bench/limits/`` are set between the program's largest and the controls'
smallest reading (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench import cells, harness

    spec = cells.load_benchmark()
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    for s in (int(s) for s in args.control_seeds.split(",") if s):
        runs += [(s, "jop_bf16"), (s, "counts_f32")]
    for seed, control in runs:
        t0 = time.perf_counter()
        out = harness.run_cell(
            spec, args.workload, seed, args.seconds, False, control=control
        )
        line = {
            "workload": args.workload,
            "seed": seed,
            "control": control,
            "correct": out["correct"],
            "attempted": out["attempted"],
            "wall_s": time.perf_counter() - t0,
            "checks": {k: v["value"] for k, v in out["checks"].items()},
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
