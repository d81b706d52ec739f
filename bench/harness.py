"""One run of one cell: set-up, the measured window, the comparison.

A closed loop with one client: the next query goes out when the previous
answer is on the host. An answer is one ``repro.serving.codesign`` call; it
is finished once its best cell, J/token and decode and prefill regime cells
have been read. Before each answer the memos the mix names are emptied, so
each answer does the work a user of that mix waits for.

Set-up answers every query of the cycle once, which compiles every program
the window runs. The window then cycles through the queries until
``seconds`` have passed. Afterwards, with the window closed and the device
memory read, the reference checks a sample of the window's answers drawn
from the seed, the answer with the most profiling jobs among them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import cells, hooks, reference, trace, traffic

# Settings that would reroute the program (numpy backend, degrade-on-error,
# an on-disk profile store, injected faults): the benchmark measures the
# default path.
CLEARED_ENV = ("REPRO_ACTIVITY_BACKEND", "REPRO_ON_ERROR", "REPRO_PROFILE_STORE", "REPRO_FAULTS")
CACHE_DIR = cells.ROOT / ".jax_cache"
# The comparison's sample: answers checked per run, and (layout, point)
# cells per checked answer priced by the plain J/op reference besides its
# best and regime cells.
CHECKED_ANSWERS = 2
PLAIN_CELLS = 12


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class RunView:
    """What the metric readers read (``bench/metrics/*.py``)."""

    cell: str
    answers: int
    window_s: float
    latencies: list[float]
    compiles: list[tuple[str, float]]
    profile_hits: int
    profile_misses: int
    kernel_calls: list[dict]
    devices: int
    trace: trace.TraceView | None
    peaks: dict | None


def _clearers(mix: dict):
    from repro.core.switching import clear_profile_cache
    from repro.layout.coeffs import clear_coeff_cache

    table = {"profile": clear_profile_cache, "lowering": clear_coeff_cache}
    return [table[name] for name in mix["clear"]]


def _configure_jax():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however small, is kept: only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _arch(cfg_doc: dict):
    from repro.configs.registry import ArchConfig

    fields = dict(cfg_doc["arch"])
    fields["stage_pattern"] = tuple(tuple(p) for p in fields["stage_pattern"])
    return ArchConfig(**fields)


def run_cell(
    spec: dict,
    cell_name: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    require_tpu: bool = True,
    t_start: float | None = None,
    mix: dict | None = None,
    cfg_doc: dict | None = None,
    limits: dict | None = None,
    control: str | None = None,
) -> dict:
    """Run one cell; returns the result line as a dict (``checks`` last).

    ``require_tpu=False`` and the ``mix``/``cfg_doc``/``limits`` overrides
    are for the tests, which drive a run on the CPU at a small size.
    ``control`` puts a reference one precision lower in the program's place
    (``bench/control.py``): ``"jop_bf16"`` runs set-up and window with the
    J/op program in bfloat16, ``"counts_f32"`` compares the counts of the
    float32-accumulating reference in place of the program's.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cells.find_cell(spec, cell_name)
    mix = mix if mix is not None else cells.load_mix(cell["traffic"])
    cfg_doc = cfg_doc if cfg_doc is not None else cells.load_config(spec, cell["config"])
    limits = limits if limits is not None else cells.load_limits(cell_name)
    for var in CLEARED_ENV:
        os.environ.pop(var, None)
    if mix.get("profile_cache_capacity"):
        os.environ["REPRO_PROFILE_CACHE_CAPACITY"] = str(mix["profile_cache_capacity"])

    jax = _configure_jax()
    devices = jax.devices()
    t_devices = time.perf_counter()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(
            f"cell {cell_name} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s) ({dev.device_kind})"
        )
    peaks = None
    if require_tpu:
        from bench.peaks import peaks_for

        peaks = peaks_for(dev.device_kind)

    from repro.core.switching import profile_cache_info, set_profile_cache_capacity
    from repro.serving.codesign import codesign

    if mix.get("profile_cache_capacity"):
        set_profile_cache_capacity(int(mix["profile_cache_capacity"]))
    arch = _arch(cfg_doc)
    clip = tuple(cfg_doc["clip"]) if cfg_doc.get("clip") else None
    families = tuple(mix["families"])
    queries = traffic.make_queries(mix, seed)
    inputs = [traffic.build_inputs(mix, q) for q in queries]
    clearers = _clearers(mix)
    hk = hooks.Hooks(traced).install()
    t_imports = time.perf_counter()
    stats = {"hits": 0, "misses": 0}

    def answer(i):
        for clear in clearers:
            clear()
        before = profile_cache_info()
        tm, space = inputs[i]
        res = codesign(arch, tm, space=space, layouts=families, clip=clip)
        # the answer is finished once these are on the host
        res.best_cell, res.j_per_token, res.regime_cell("decode"), res.regime_cell("prefill")
        after = profile_cache_info()
        return res, after["hits"] - before["hits"], after["misses"] - before["misses"]

    stack = contextlib.ExitStack()
    try:
        if control == "jop_bf16":
            stack.enter_context(reference.bf16_jop_program())
        warm = []
        for i in range(len(queries)):
            t0 = time.perf_counter()
            answer(i)
            hk.take()
            warm.append(time.perf_counter() - t0)
        setup_s = time.perf_counter() - t_start
        n_setup_compiles = len(hk.compiles)

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        slots: dict[int, tuple] = {}
        latencies: list[float] = []
        failures: list[str] = []
        hk.kernel_calls.clear()
        if traced:
            trace.start(trace_dir)
        w0 = time.perf_counter()
        with hk.span(trace.WINDOW_SPAN):
            i = 0
            while time.perf_counter() - w0 < seconds:
                q = i % len(queries)
                t0 = time.perf_counter()
                try:
                    res, hit, miss = answer(q)
                except Exception as exc:  # a failed answer counts, the loop goes on
                    failures.append(f"{type(exc).__name__}: {exc}")
                    hk.take()
                else:
                    latencies.append(time.perf_counter() - t0)
                    stats["hits"] += hit
                    stats["misses"] += miss
                    slots[q] = (res, hk.take())
                i += 1
        window_s = time.perf_counter() - w0
        if traced:
            trace.stop()
        window_compiles = [(n, d) for t, n, d in hk.compiles[n_setup_compiles:] if t >= w0]
        kernel_calls = list(hk.kernel_calls)
    finally:
        stack.close()
        hk.uninstall()

    view = None
    if traced:
        view = trace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = 0
    for d in jax.local_devices():
        mem = d.memory_stats() or {}
        peak = max(peak, int(mem.get("peak_bytes_in_use", 0)))

    run = RunView(
        cell=cell_name,
        answers=len(latencies),
        window_s=window_s,
        latencies=latencies,
        compiles=window_compiles,
        profile_hits=stats["hits"],
        profile_misses=stats["misses"],
        kernel_calls=kernel_calls,
        devices=cell["chips"],
        trace=view,
        peaks=peaks,
    )
    spaces = [space for _, space in inputs]
    checks = check_answers(slots, spaces, seed, limits, counts_control=control == "counts_f32")
    checks["failed_answers"] = {"value": len(failures), "limit": 0}
    correct = bool(latencies) and all(c["value"] <= c["limit"] for c in checks.values())

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cells.cell_metrics(spec, cell_name, kind):
        if m["name"] == "setup_s":
            value = setup_s
        elif traced:
            value = cells.metric_reader(m["name"])(run)
        else:
            value = END_TO_END[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": correct,
        "attempted": len(latencies) + len(failures),
        "failed": len(failures),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        lo, hi = view.window()
        n = max(1, cell["chips"])
        device["busy_s"] = sum(trace.busy_ns(view, d) for d in range(n)) / n * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = trace.breakdown(view)
    print(
        f"set-up: {t_devices - t_start:.2f} s to the devices, {t_imports - t_devices:.2f} s "
        f"to import the program and build the queries, warm answers {[round(x, 2) for x in warm]} s, "
        f"{n_setup_compiles} compiles or compile-cache loads in them, "
        f"{sum(d for _, _, d in hk.compiles[:n_setup_compiles]):.2f} s",
        file=sys.stderr,
    )
    if failures:
        print("failed answers: " + " | ".join(failures[:5]), file=sys.stderr)
    if traced:
        names: dict[str, int] = {}
        for name, _ in window_compiles:
            names[name] = names.get(name, 0) + 1
        print(f"compiles in the window: {names}", file=sys.stderr)
    out["checks"] = checks
    return out


def check_answers(
    slots: dict, spaces: list, seed: int, limits: dict, *, counts_control: bool = False
) -> dict:
    """Compare a sample of the window's answers with the references.

    The sample is the last answer of ``CHECKED_ANSWERS`` of the queries:
    the one whose profiling ran the most jobs, and others drawn from the
    seed. ``spaces`` are the queries' design spaces.
    """
    if not slots:
        return {}
    from repro.core.switching import clear_profile_cache
    from repro.layout.coeffs import clear_coeff_cache

    clear_profile_cache()
    clear_coeff_cache()
    n_jobs = {q: sum(len(j) for j, _ in rec.get("profile_batches", [])) for q, (_, rec) in slots.items()}
    order = sorted(slots)
    longest = max(order, key=lambda q: (n_jobs[q], -q))
    rest = [q for q in order if q != longest]
    rng = np.random.default_rng([int(seed), 1])
    k = min(len(order), CHECKED_ANSWERS) - 1
    sample = [longest] + [rest[i] for i in sorted(rng.choice(len(rest), size=k, replace=False))]
    memo: dict = {}
    bad = total = 0
    gap = plain = 0.0
    for q in sample:
        res, rec = slots[q]
        if "jobset" not in rec or "pricing" not in rec:  # never reached a layer
            bad, gap, plain = bad + 1, float("inf"), float("inf")
            continue
        b, t = reference.count_mismatches(
            rec.get("profile_batches", []), reference.job_densities(rec["jobset"]), memo,
            accumulate=np.float32 if counts_control else np.int64,
            program=not counts_control,
        )
        bad, total = bad + b, total + t
        plain = max(plain, reference.jop_plain_gap(res, rec["pricing"], spaces[q], rng, PLAIN_CELLS))
        gap = max(gap, reference.jop_gap(res, reference.price_f64(rec["pricing"])))
    return {
        "count_mismatch_jobs": {
            "value": bad if total else float("inf"),
            "limit": limits["count_mismatch_jobs"],
        },
        "jop_plain_gap": {"value": plain, "limit": limits["jop_plain_gap"]},
        "jop_rel_gap": {"value": gap, "limit": limits["jop_rel_gap"]},
    }


def _p90(lat):
    return float(np.percentile(np.asarray(lat), 90)) if lat else None


END_TO_END = {
    "answer_s": lambda r: r.window_s / r.answers if r.answers else None,
    "answer_p90_s": lambda r: _p90(r.latencies),
}
