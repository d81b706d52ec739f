#!/usr/bin/env python3
"""Run one benchmark cell of ``repro.serving.codesign`` on the chip.

    python3 bench/run.py --workload mixtral_8x7b.oneshot --seed 7 --seconds 10 --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit. The checks are also the last lines of standard error.

Exits 3 without a result when JAX finds no TPU, or fewer chips than the
cell asks for, and 2 when the repository's program is not beside it.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells, harness

    try:
        import repro  # noqa: F401
    except ImportError as exc:
        print(f"bench: the repository's program is not beside the benchmark ({exc})",
              file=sys.stderr)
        return 2
    spec = cells.load_benchmark()
    try:
        out = harness.run_cell(
            spec, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
        )
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(out)), flush=True)
    return 0


def finite(x):
    """JSON has no infinity: a reading with no finite value prints as null."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    sys.exit(main())
