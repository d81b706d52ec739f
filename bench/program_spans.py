#!/usr/bin/env python3
"""The program's own spans in a traced run, and what is read from them.

The program marks its phases with host spans named ``repro.*``
(``src/repro/tracing.py``): ``repro.codesign`` around each answer, and
inside it ``repro.expand``, ``repro.profile`` with its phases
``repro.profile.<phase>`` (``jobs``, ``setup``, ``synth_wait``, ``check``,
``key``, ``schedule``, ``stack``, ``collect``, ``assemble``),
``repro.lower.<function>`` and ``repro.price``.
``load`` keeps them from a profiler trace as ``(start_ns, end_ns, name,
thread)``, where ``thread`` numbers the trace's host lines (one per thread).
The main thread is the one that opens ``repro.codesign``; synthesis
(``repro.profile.synthesize``) and device dispatch
(``repro.profile.dispatch``) run on worker threads.

``READERS`` are the per-answer readings of activity profiling, each taken
over the measured window: a number, or None where the run holds nothing to
read. ``profile_cover`` is the share of the main thread's ``repro.profile``
time that its named phases cover. ``idle_by_program_span`` sums device 0's
idle time by the innermost main-thread span the host was in.

The harness's trace view keeps only its own ``bench.*`` spans, so none of
this is a metric of BENCHMARK.json yet. Run as a script, this file runs one
cell traced through the harness, keeps the program's spans and each window
answer's ``profile_stats`` besides, and prints the harness's result line with
a ``program`` object added (the readings, the cover, the idle split and the
traced ``answer_s``):

    python3 bench/program_spans.py --workload mixtral_8x7b.oneshot --seed 7 --seconds 20
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.trace import DEVICE_PLANE, covered, idle_gaps, merged  # noqa: E402

PREFIX = "repro."
ROOT_SPAN = "repro.codesign"
PROFILE_SPAN = "repro.profile"
OUTSIDE = "outside-spans"


def load(log_dir: str) -> list[tuple[float, float, str, int]]:
    """Host events named ``repro.*`` of the trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    out, thread = [], 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend(
                (e.start_ns, e.end_ns, e.name, thread)
                for e in line.events
                if e.name.startswith(PREFIX)
            )
            thread += 1
    out.sort()
    return out


def main_thread(program) -> int | None:
    """The thread that opens the most ``repro.codesign`` spans."""
    counts: dict[int, int] = {}
    for _, _, name, thread in program:
        if name == ROOT_SPAN:
            counts[thread] = counts.get(thread, 0) + 1
    return max(counts, key=counts.get) if counts else None


def _spans(program, names, *, main_only: bool = True):
    main = main_thread(program)
    return [
        (s, e) for s, e, n, t in program
        if n in names and (not main_only or t == main)
    ]


def _per_answer_ms(spans, window, answers):
    if not spans or not answers:
        return None
    return covered(spans, *window) * 1e-6 / answers


def synth_ms(program, window, answers, stats):
    """Synthesis busy time: the union of ``repro.profile.synthesize`` over
    all threads."""
    spans = _spans(program, {"repro.profile.synthesize"}, main_only=False)
    return _per_answer_ms(spans, window, answers)


def synth_wait_ms(program, window, answers, stats):
    """Synthesis on the critical path: the main thread waiting for operands."""
    return _per_answer_ms(_spans(program, {"repro.profile.synth_wait"}), window, answers)


def key_ms(program, window, answers, stats):
    """Content hashing for the profile cache (lookup and store keys)."""
    return _per_answer_ms(_spans(program, {"repro.profile.key"}), window, answers)


def prep_ms(program, window, answers, stats):
    """Host preparation of device inputs: strip and tile cutting, stacking."""
    spans = _spans(program, {"repro.profile.schedule", "repro.profile.stack"})
    return _per_answer_ms(spans, window, answers)


def collect_ms(program, window, answers, stats):
    """The main thread waiting on compile, kernels and transfer."""
    return _per_answer_ms(_spans(program, {"repro.profile.collect"}), window, answers)


def programs_per_answer(program, window, answers, stats):
    """Fused device programs dispatched (``BatchStats.buckets``) per answer."""
    total = sum(s["buckets"] for s in stats)
    if not total or not answers:
        return None
    return total / answers


READERS = {
    "profile_synth_ms": synth_ms,
    "profile_synth_wait_ms": synth_wait_ms,
    "profile_key_ms": key_ms,
    "profile_prep_ms": prep_ms,
    "profile_collect_ms": collect_ms,
    "profile_programs_per_answer": programs_per_answer,
}


def profile_cover(program, window) -> float | None:
    """Share of the main thread's ``repro.profile`` time, in the window,
    inside one of its ``repro.profile.<phase>`` spans."""
    main = main_thread(program)
    profile = merged(
        [(s, e) for s, e, n, t in program if n == PROFILE_SPAN and t == main], *window
    )
    phases = merged(
        [(s, e) for s, e, n, t in program
         if n.startswith(PROFILE_SPAN + ".") and t == main],
        *window,
    )
    total = sum(e - s for s, e in profile)
    if total <= 0:
        return None
    inside, j = 0.0, 0
    for s, e in profile:  # both sorted and disjoint
        while j < len(phases) and phases[j][1] <= s:
            j += 1
        k = j
        while k < len(phases) and phases[k][0] < e:
            inside += min(e, phases[k][1]) - max(s, phases[k][0])
            k += 1
    return inside / total


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint pieces ``(start, end, name)`` of one thread's nested spans:
    at each moment, the innermost span open then."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name), innermost last
    t = -math.inf

    def close_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack and s > t:
            pieces.append((t, s, stack[-1][1]))
        stack.append((e, n))
        t = max(t, s)
    close_until(math.inf)
    return pieces


def idle_by_program_span(view, program) -> dict[str, float]:
    """Device 0's idle seconds in the window, summed by the innermost
    main-thread ``repro.*`` span open at the time (``outside-spans`` where
    none is), largest first."""
    main = main_thread(program)
    pieces = innermost([(s, e, n) for s, e, n, t in program if t == main])
    out: dict[str, float] = {}
    i = 0
    for gs, ge in idle_gaps(view):
        inside = 0.0
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            s, e, name = pieces[j]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
                inside += d
            j += 1
        if ge - gs > inside:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (ge - gs - inside) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def span_ms(program, window, answers) -> dict[str, float]:
    """Main-thread time per answer of each span name (the union of its
    spans, so a name nested in itself counts once)."""
    main = main_thread(program)
    names = sorted({n for _, _, n, t in program if t == main})
    return {
        n: covered([(s, e) for s, e, m, t in program if m == n and t == main], *window)
        * 1e-6 / answers
        for n in names
    }


def summary(view, program, stats) -> dict:
    """What a traced run shows of the program's spans: ``stats`` are the
    window answers' ``profile_stats`` as dicts."""
    window = view.window()
    answers = len(stats)
    out = {
        "answers": answers,
        "answer_s_traced": (window[1] - window[0]) * 1e-9 / answers if answers else None,
        "readings": {k: f(program, window, answers, stats) for k, f in READERS.items()},
        "profile_cover": profile_cover(program, window),
        "idle_by_span_s": idle_by_program_span(view, program),
    }
    if answers:
        out["span_ms"] = span_ms(program, window, answers)
    return out


def run(workload: str, seed: int, seconds: float, **kw) -> dict:
    """One traced run of the cell through the harness; the result line with
    ``program`` (``summary``) added."""
    from bench import cells, harness, trace

    # the module, which the package's ``codesign`` function shadows
    codesign_mod = importlib.import_module("repro.serving.codesign")

    kept: dict = {"stats": []}
    orig = (trace.load, trace.start, trace.stop, codesign_mod.codesign)

    def load_both(log_dir):
        kept["program"] = load(log_dir)
        kept["view"] = orig[0](log_dir)
        return kept["view"]

    def start(log_dir):
        orig[1](log_dir)
        kept["t0"] = time.perf_counter()

    def stop():
        kept["t1"] = time.perf_counter()
        orig[2]()

    def codesign(*args, **kwargs):
        res = orig[3](*args, **kwargs)
        if "t0" in kept and "t1" not in kept:
            stats = getattr(res, "profile_stats", None)  # absent before the spans
            kept["stats"].append(stats.as_dict() if stats is not None else {"buckets": 0})
        return res

    trace.load, trace.start, trace.stop, codesign_mod.codesign = (
        load_both, start, stop, codesign,
    )
    try:
        out = harness.run_cell(cells.load_benchmark(), workload, seed, seconds, True, **kw)
    finally:
        trace.load, trace.start, trace.stop, codesign_mod.codesign = orig
    checks = out.pop("checks")
    out["program"] = summary(kept["view"], kept["program"], kept["stats"])
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, run as run_mod

    try:
        out = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    prog = out["program"]
    print("idle by program span (s): " + json.dumps(prog["idle_by_span_s"]), file=sys.stderr)
    print(json.dumps(run_mod.finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
