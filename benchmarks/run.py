"""Benchmark harness: one module per paper table/figure (+ kernel layer).

Prints ``name,us_per_call,derived`` CSV. Exit code 1 if any module fails.

``python -m benchmarks.run --smoke`` runs every module in its cheap
configuration (subsampled profiles, fewer repeats) — a CI-sized smoke pass
(including the OS rows of bench_design_space and the OS jobs of
bench_network_profile).
``--json PATH`` additionally writes the rows (plus per-module status) as a
JSON document; CI uploads it as a workflow artifact so regressions can be
diffed across runs.  Each JSON row records a ``dataflow`` field ("WS",
"OS", "WS+OS", or "" when the row is dataflow-agnostic), a ``layout``
field (a layout-family name, "+"-joined names, or "" when the row is
layout-agnostic), a ``cells_per_s`` field (warm coefficient-evaluator
throughput; 0.0 for rows that don't measure it), and a ``sweep`` field
({} unless the row ran through the chunked sweep runner, in which case it
carries the machine-readable ``SweepReport`` dicts: chunks
evaluated/resumed/quarantined, guard verdicts, rung counts, failure
records).
"""

from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys
import time
import traceback

from benchmarks import (
    bench_activity_profile,
    bench_aspect_sweep,
    bench_design_space,
    bench_fig4_fig5_power,
    bench_kernels,
    bench_layout,
    bench_mxu_scale,
    bench_network_profile,
    bench_objective,
    bench_resilience,
    bench_serving,
    bench_table1_layers,
)
from repro.compile_cache import configure_compile_cache

MODULES = [
    ("aspect_sweep", bench_aspect_sweep),
    ("table1_layers", bench_table1_layers),
    ("fig4_fig5_power", bench_fig4_fig5_power),
    ("mxu_scale", bench_mxu_scale),
    ("design_space", bench_design_space),
    ("layout", bench_layout),
    ("objective", bench_objective),
    ("serving", bench_serving),
    ("kernels", bench_kernels),
    ("activity_profile", bench_activity_profile),
    ("network_profile", bench_network_profile),
    ("resilience", bench_resilience),
]


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke", action="store_true", help="cheap configuration for CI smoke runs"
    )
    parser.add_argument(
        "--json", metavar="PATH", default=None, help="also write results as JSON"
    )
    args = parser.parse_args(argv)
    configure_compile_cache()

    print("name,us_per_call,derived")
    failed = False
    t_run = time.perf_counter()
    report: dict = {"smoke": args.smoke, "modules": {}, "rows": []}
    for name, mod in MODULES:
        try:
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            for row in mod.run(**kwargs):
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']},{derived}")
                report["rows"].append(
                    {
                        "name": row["name"],
                        "us_per_call": float(row["us_per_call"]),
                        "derived": str(row["derived"]),
                        "dataflow": str(row.get("dataflow", "")),
                        "layout": str(row.get("layout", "")),
                        # warm throughput of the coefficient-protocol
                        # evaluator (0.0 for rows that don't measure it) —
                        # the CI perf-floor job tracks this trajectory
                        "cells_per_s": float(row.get("cells_per_s", 0.0)),
                        # J/op-vs-bus-power ranking disagreements (the
                        # objective/winner_flips row; 0 elsewhere)
                        "flips": int(row.get("flips", 0)),
                        # chunked-sweep accounting (chunks evaluated /
                        # resumed / quarantined, guard verdicts) — the CI
                        # sweep-resume and chaos jobs assert against these
                        "sweep": row.get("sweep", {}),
                    }
                )
            report["modules"][name] = "ok"
        except Exception:
            failed = True
            err = traceback.format_exc(limit=1).splitlines()[-1]
            print(f"{name},ERROR,{err}")
            report["modules"][name] = f"ERROR: {err}"
    report["failed"] = failed
    report["wall_s"] = round(time.perf_counter() - t_run, 3)
    # Persistent-store accounting: with $REPRO_PROFILE_STORE set, a warm
    # run's JSON proves it skipped re-profiling (store hits > 0, zero
    # integrity failures) — the CI cold->warm job asserts exactly this.
    from repro.core.switching import profile_cache_info, profile_store_info
    from repro.layout import coeff_cache_info

    report["profile_cache"] = profile_cache_info()
    report["profile_store"] = profile_store_info()
    # Coefficient-lowering memo accounting: hits prove repeated sweeps over
    # the same (grid, layouts) reuse the lowered arrays instead of re-lowering
    report["coeff_cache"] = coeff_cache_info()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        # Repo-root trajectory snapshot: the per-PR row dump CI uploads so
        # throughput (cells_per_s) and flip counts diff across PRs.
        bench_pr = pathlib.Path(__file__).resolve().parent.parent / "BENCH_10.json"
        with open(bench_pr, "w") as f:
            json.dump({"pr": 10, "rows": report["rows"]}, f, indent=1)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
