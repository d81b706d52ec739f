#!/usr/bin/env python3
"""Chip smoke test: serving co-design end to end on a TPU.

    python chip_smoke.py             # one chip (the phases below)
    python chip_smoke.py --chips 4   # the four-chip sharding phase only

One-chip phases, each printing one line:

  device      fails unless JAX's first device is a TPU.
  codesign    ``codesign("mixtral_8x7b", "decode_heavy")`` with its defaults,
              cold then warm (host wall clock), its profiling run by the
              Pallas kernels; the answer matches the float64 host evaluator
              (``use_jit=False``).
  parity      the same job set through ``run_profile_batch``: Pallas
              (``engine="auto"``) vs the XLA rendering on the chip vs the
              numpy oracle, bit-exact, nothing degraded or run serially.
  full-width  one unclipped Mixtral expert-FFN GEMM (K=4096, N=14336, the job
              set's own M) on a 32x128 array, WS and OS: Pallas vs XLA,
              batched and per-GEMM engines, bit-exact.

``--chips 4`` needs a host with four chips and runs one phase: the batched
profiler sharding each bucket's tasks over the four devices vs one device
(bit-exact), and a chunked ``evaluate_fleet_objective`` spread over the four
devices vs the same chunks on one device (bit-identical) and vs the
unchunked program (the same best cell; any difference is printed and must
stay within the J/token tolerance).

A numpy fallback (``ProfileDegradationWarning``) is an error. Any failure
exits nonzero without the result line. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import warnings
from pathlib import Path

ARCH, TRAFFIC = "mixtral_8x7b", "decode_heavy"
# Relative tolerance on J/token, float32 device program vs float64 host
# evaluator. The program is elementwise float32 arithmetic plus sums over
# the 72 GEMMs and a Newton aspect search: a few hundred roundings of unit
# roundoff 6e-8 bound the error near 1e-5 at worst; measured, the two differ
# by 1.4e-8 on a TPU v5e and on a CPU. The best cell leads the runner-up by
# about 1e-3, so no winner can flip inside this tolerance.
J_PER_TOKEN_RTOL = 1e-5
FULL_ROWS, FULL_COLS = 32, 128

# Settings that would reroute the profiler (numpy backend, degrade-on-error,
# an on-disk profile store, injected faults) are cleared: this script
# measures the default path.
for _var in ("REPRO_ACTIVITY_BACKEND", "REPRO_ON_ERROR", "REPRO_PROFILE_STORE", "REPRO_FAULTS"):
    os.environ.pop(_var, None)
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def counts(p) -> tuple[int, int, int, int]:
    """Exact integer toggle totals back out of a profile (the activities
    are integer ratios held in float64 far below 2^53)."""
    return (
        round(p.a_h * p.h_transitions * p.b_h),
        round(p.a_v * p.v_transitions * p.b_v),
        p.h_transitions,
        p.v_transitions,
    )


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_codesign() -> None:
    from repro.kernels.activity_profile import batch, kernel
    from repro.serving.codesign import codesign

    def answer(**kw):
        res = codesign(ARCH, TRAFFIC, **kw)
        # the properties pull the priced block to the host: inside the timing
        return res, res.best_cell, res.j_per_token

    (res, cell, jpt), cold_s = timed(answer)
    check(
        kernel.activity_profile_pallas_tasks._cache_size() > 0
        and kernel.stream_strips_toggles_pallas._cache_size() > 0
        and batch._bucket_counts_xla._cache_size() == 0,
        "codesign's profiling did not run the Pallas kernels",
    )
    (_, warm_cell, warm_jpt), warm_s = timed(answer)
    host, host_cell, host_jpt = answer(use_jit=False)
    regimes = {r: res.regime_cell(r) for r in ("decode", "prefill")}
    host_regimes = {r: host.regime_cell(r) for r in ("decode", "prefill")}
    rel = abs(jpt - host_jpt) / abs(host_jpt)
    say(
        "codesign",
        f"{ARCH} x {TRAFFIC}: cold {cold_s:.3f} s, warm {warm_s:.3f} s "
        "(single-run host wall clock)",
    )
    say(
        "codesign",
        f"chip: best {res.describe_cell(cell)} J/token={jpt!r} "
        f"decode={res.describe_cell(regimes['decode'])} "
        f"prefill={res.describe_cell(regimes['prefill'])}",
    )
    say(
        "codesign",
        f"f64 host: best {host.describe_cell(host_cell)} J/token={host_jpt!r} "
        f"decode={host.describe_cell(host_regimes['decode'])} "
        f"prefill={host.describe_cell(host_regimes['prefill'])}",
    )
    check(warm_cell == cell and warm_jpt == jpt, "warm answer differs from cold")
    check(cell == host_cell, f"best cell {cell} != host {host_cell}")
    check(regimes == host_regimes, f"regime cells {regimes} != host {host_regimes}")
    check(rel <= J_PER_TOKEN_RTOL, f"J/token rel err {rel:.3g} > {J_PER_TOKEN_RTOL}")
    say("codesign", f"ok: J/token rel err {rel:.3g} <= {J_PER_TOKEN_RTOL}")


def _jobset_jobs():
    from repro.core.workloads import design_gemm_jobs
    from repro.serving.codesign import DEFAULT_SPACE
    from repro.serving.traffic import get_preset, weighted_gemms
    from repro.configs.registry import get_arch

    jobset = weighted_gemms(get_arch(ARCH), get_preset(TRAFFIC))
    jobs, _, _ = design_gemm_jobs(
        DEFAULT_SPACE.expand(), jobset.gemms, densities=jobset.densities
    )
    return jobset, jobs


def _clean(stats, what: str) -> None:
    check(
        stats.degraded == stats.serial_fallbacks == stats.skipped == 0,
        f"{what}: degraded={stats.degraded} serial={stats.serial_fallbacks} "
        f"skipped={stats.skipped}",
    )


def phase_parity() -> None:
    from repro.core.pipeline import run_profile_batch
    from repro.kernels.activity_profile import batch
    from repro.kernels.activity_profile.ref import profile_gemm_toggles_ref

    _, jobs = _jobset_jobs()
    kw = dict(on_error="raise", use_cache=False)
    (auto, st_auto), t_auto = timed(lambda: run_profile_batch(jobs, engine="auto", **kw))
    _clean(st_auto, "engine=auto")
    check(
        batch._bucket_counts_xla._cache_size() == 0,
        "engine=auto ran the XLA rendering, not the Pallas kernel",
    )
    (xla, st_xla), t_xla = timed(lambda: run_profile_batch(jobs, engine="xla", **kw))
    _clean(st_xla, "engine=xla")
    bad = [j.name for j, p, q in zip(jobs, auto, xla) if counts(p) != counts(q)]
    check(not bad, f"Pallas != XLA on {bad}")
    t0 = time.perf_counter()
    for job, p in zip(jobs, auto):
        a, w = job.operands()
        ref = profile_gemm_toggles_ref(
            a, w, job.rows, job.cols, job.b_h, job.b_v, dataflow=job.dataflow
        )
        check(counts(p) == ref, f"Pallas != numpy oracle on {job.name} {job.dataflow}")
    t_ref = time.perf_counter() - t0
    n_ws = sum(j.dataflow == "WS" for j in jobs)
    say(
        "parity",
        f"ok: {len(jobs)} jobs ({n_ws} WS, {len(jobs) - n_ws} OS), "
        f"{st_auto.buckets} buckets, {st_auto.tasks} tasks, {st_auto.strips} strips; "
        f"Pallas == XLA == numpy oracle on all; Pallas {t_auto:.3f} s, "
        f"XLA {t_xla:.3f} s, oracle {t_ref:.3f} s (compile included)",
    )


def phase_full_width() -> None:
    from repro.core.floorplan import accumulator_width
    from repro.core.pipeline import run_profile_batch
    from repro.core.workloads import gemm_job, gemm_profile_seed
    from repro.kernels.activity_profile.ops import profile_gemm_toggles

    jobset, _ = _jobset_jobs()
    expert = [
        (w, g)
        for g, w in zip(jobset.gemms, jobset.weights)
        if ".moe.expert_" in g.name and (g.k, g.n) == (4096, 14336)
    ]
    _, g = max(expert, key=lambda x: x[0])
    seed = gemm_profile_seed(g, clip=None)
    say(
        "full-width",
        f"{g.name} M={g.m} K={g.k} N={g.n} (the job set's own M, no cut) "
        f"on {FULL_ROWS}x{FULL_COLS}",
    )
    for df in ("WS", "OS"):
        job = gemm_job(g, FULL_ROWS, FULL_COLS, 16, seed=seed, clip=None, dataflow=df)
        kw = dict(on_error="raise", use_cache=False)
        ((pal,), st), t_pal = timed(lambda: run_profile_batch([job], engine="pallas", **kw))
        _clean(st, f"{df} pallas")
        ((xla,), st), t_xla = timed(lambda: run_profile_batch([job], engine="xla", **kw))
        _clean(st, f"{df} xla")
        check(counts(pal) == counts(xla), f"{df} batched Pallas != XLA")
        a, w = job.operands()
        b_v = accumulator_width(16, FULL_ROWS) if df == "WS" else 16
        per = {}
        for engine in ("pallas", "xla"):
            c, t = timed(
                lambda: profile_gemm_toggles(
                    a, w, FULL_ROWS, FULL_COLS, 16, b_v, dataflow=df, engine=engine
                )
            )
            per[engine] = ((c.h_toggles, c.v_toggles, c.h_transitions, c.v_transitions), t)
        check(
            per["pallas"][0] == per["xla"][0] == counts(pal),
            f"{df} per-GEMM engines disagree: {per} vs batched {counts(pal)}",
        )
        say(
            "full-width",
            f"ok {df}: counts {counts(pal)} (h, v, h_trans, v_trans) equal on "
            f"batched Pallas {t_pal:.3f} s / XLA {t_xla:.3f} s and per-GEMM "
            f"Pallas {per['pallas'][1]:.3f} s / XLA {per['xla'][1]:.3f} s "
            f"({st.tasks} tasks, {st.strips} strips; compile included)",
        )


def phase_four_chips(devices) -> None:
    import numpy as np

    from repro.core.objective import evaluate_fleet_objective
    from repro.core.pipeline import run_profile_batch
    from repro.core.sweep import SweepConfig
    from repro.core.workloads import measured_design_gemm_activities
    from repro.serving.codesign import DEFAULT_FAMILIES, DEFAULT_SPACE

    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    jobset, jobs = _jobset_jobs()
    kw = dict(on_error="raise", use_cache=False)
    (one, st1), t1 = timed(lambda: run_profile_batch(jobs, devices=devices[:1], **kw))
    (four, st4), t4 = timed(lambda: run_profile_batch(jobs, devices=devices, **kw))
    _clean(st1, "one device")
    _clean(st4, "four devices")
    # a bucket splits into min(devices, tasks // 64) shards
    check(st4.tasks >= 64 * 4, f"only {st4.tasks} tasks: no bucket splits four ways")
    bad = [j.name for j, p, q in zip(jobs, one, four) if counts(p) != counts(q)]
    check(not bad, f"four-device counts != one-device counts on {bad}")
    say(
        "four-chips",
        f"profiling ok: {len(jobs)} jobs, {st4.tasks} tasks in {st4.buckets} "
        f"buckets sharded over 4 devices == 1 device; 1 device {t1:.3f} s, "
        f"4 devices {t4:.3f} s (single-run wall clock, compile included)",
    )

    grid = DEFAULT_SPACE.expand()
    a_h, a_v = measured_design_gemm_activities(
        grid, jobset.gemms, densities=jobset.densities, use_cache=False
    )
    args = (grid, a_h, a_v, jobset.gemms)
    ekw = dict(
        layouts=DEFAULT_FAMILIES,
        weights=jobset.weights,
        macs_per_token=jobset.macs_per_token,
        use_jit=True,
    )
    fields = (
        "feasible", "aspect_lo", "aspect_hi", "aspect_opt", "bus_power_opt",
        "aspect_robust", "bus_power_robust", "overhead_w", "wirelength_um",
        "utilization", "j_per_mac", "j_per_mac_robust",
    )

    def chunked(chunk_size, devs):
        sweep = SweepConfig(chunk_size=chunk_size, on_violation="raise", devices=tuple(devs))
        ev = evaluate_fleet_objective(*args, sweep=sweep, **ekw)
        rep = ev.sweep_report
        check(
            rep.rung_counts() == {"jit": rep.chunks_total},
            f"chunks did not all run jitted on the devices: {rep.rung_counts()}",
        )
        return ev, rep.chunks_total

    def differing(x_ev, y_ev):
        """{field: max relative difference} over the fields not bit-identical."""
        out = {}
        for f in fields:
            x, y = np.asarray(getattr(x_ev, f)), np.asarray(getattr(y_ev, f))
            check(x.dtype == y.dtype and x.shape == y.shape, f"{f} dtype/shape differ")
            if x.tobytes() == y.tobytes():
                continue
            check(
                x.dtype != bool and np.array_equal(np.isfinite(x), np.isfinite(y)),
                f"{f} differs in feasibility or finiteness",
            )
            fin = np.isfinite(x)
            rel = np.abs(x[fin] - y[fin]) / np.maximum(np.abs(y[fin]), 1e-300)
            out[f] = float(rel.max())
        return out

    def best(ev):
        j = np.asarray(ev.j_per_mac_robust)
        return np.unravel_index(np.argmin(j), j.shape)

    plain = evaluate_fleet_objective(*args, **ekw)
    four, n_chunks = chunked(8, devices)
    one, _ = chunked(8, devices[:1])
    whole, _ = chunked(grid.n_points, devices[:1])
    check(n_chunks >= 4, f"only {n_chunks} chunks: not every device got one")
    check(
        not differing(four, one),
        f"chunks over 4 devices differ from the same chunks on one device: "
        f"{differing(four, one)}",
    )
    say(
        "four-chips",
        f"sweep: {grid.n_points} points in {n_chunks} chunks round-robin over 4 "
        f"devices == the same chunks on one device, bit-identical in {len(fields)} fields",
    )
    for name, ev in (("8-point chunks", four), ("one 40-point chunk", whole)):
        diff = differing(ev, plain)
        check(best(ev) == best(plain), f"{name}: best cell differs from unchunked")
        check(
            all(v <= J_PER_TOKEN_RTOL for v in diff.values()),
            f"{name}: beyond rtol {J_PER_TOKEN_RTOL}: {diff}",
        )
        say(
            "four-chips",
            f"sweep: {name} vs the unchunked program on one device: "
            + ("bit-identical" if not diff else f"same best cell; not bit-identical, "
               f"max rel diff per field {diff}"),
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip sharding phase",
    )
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import configure_compile_cache
        from repro.runtime.resilience import ProfileDegradationWarning
    except ImportError as exc:
        print(f"chip_smoke: the repository's src/ is not beside this script ({exc})",
              file=sys.stderr)
        return 2
    warnings.simplefilter("error", ProfileDegradationWarning)
    cache_dir = configure_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX's first device is {dev.platform}: "
            f"{dev.device_kind}); this smoke test does not run on the CPU",
            file=sys.stderr,
        )
        return 1
    say("device", f"platform=tpu kind={dev.device_kind} count={len(devices)} "
        f"compile cache={cache_dir}")

    if args.chips == 4:
        phases = [("four-chips", lambda: phase_four_chips(devices))]
    else:
        phases = [
            ("codesign", phase_codesign),
            ("parity", phase_parity),
            ("full-width", phase_full_width),
        ]
    for name, fn in phases:
        try:
            _, secs = timed(fn)
        except Exception:
            say(name, "FAILED")
            traceback.print_exc()
            sys.stdout.flush()
            return 1
        say(name, f"phase wall {secs:.3f} s")
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                "count": len(devices)}}
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
