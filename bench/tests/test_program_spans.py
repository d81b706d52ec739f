"""The readers of the program's own spans (``bench/program_spans.py``) on a
hand-made trace with known answers, on the recorded v5e trace (which holds
none), and on a tiny cell run on the CPU."""

import json

import pytest

from bench import program_spans as ps
from bench import trace

from .conftest import DATA

MS = 1_000_000  # ns
MAIN, PREFETCH, WORKER = 0, 1, 2


def hand_program():
    """Two answers in a 100 ms window; device 0 busy 20..30 and 70..75 ms."""
    return [
        (0, 40 * MS, "repro.codesign", MAIN),
        (1 * MS, 30 * MS, "repro.profile", MAIN),
        (1 * MS, 2 * MS, "repro.profile.jobs", MAIN),
        (2 * MS, 6 * MS, "repro.profile.synth_wait", MAIN),
        (2 * MS, 8 * MS, "repro.profile.synthesize", PREFETCH),
        (6 * MS, 9 * MS, "repro.profile.key", MAIN),
        (9 * MS, 12 * MS, "repro.profile.schedule", MAIN),
        (11 * MS, 14 * MS, "repro.profile.stack", MAIN),  # overlaps schedule: counts once
        (14 * MS, 26 * MS, "repro.profile.collect", MAIN),
        (15 * MS, 25 * MS, "repro.profile.dispatch", WORKER),
        (27 * MS, 28 * MS, "repro.profile.assemble", MAIN),
        (30 * MS, 40 * MS, "repro.price", MAIN),
        (50 * MS, 90 * MS, "repro.codesign", MAIN),
        (50 * MS, 80 * MS, "repro.profile", MAIN),
        (50 * MS, 56 * MS, "repro.profile.synthesize", PREFETCH),
        (51 * MS, 55 * MS, "repro.profile.synth_wait", MAIN),
        (55 * MS, 57 * MS, "repro.profile.key", MAIN),
        (57 * MS, 75 * MS, "repro.profile.collect", MAIN),
        (80 * MS, 90 * MS, "repro.price", MAIN),
    ]


def hand_view():
    return trace.TraceView(
        ops={0: [(20 * MS, 30 * MS, "k"), (70 * MS, 75 * MS, "k")]},
        modules={},
        spans=[(0, 100 * MS, "bench.window")],
    )


STATS = [{"buckets": 3, "jobs": 10}, {"buckets": 1, "jobs": 10}]


def readings(program=None, stats=STATS):
    program = hand_program() if program is None else program
    return {k: f(program, (0, 100 * MS), 2, stats) for k, f in ps.READERS.items()}


def test_readers_per_answer():
    r = readings()
    assert r["profile_synth_ms"] == pytest.approx((6 + 6) / 2)  # both threads
    assert r["profile_synth_wait_ms"] == pytest.approx((4 + 4) / 2)
    assert r["profile_key_ms"] == pytest.approx((3 + 2) / 2)
    assert r["profile_prep_ms"] == pytest.approx(5 / 2)
    assert r["profile_collect_ms"] == pytest.approx((12 + 18) / 2)
    assert r["profile_programs_per_answer"] == pytest.approx(2.0)


def test_readers_read_only_the_main_thread():
    """The main thread is the one that opens ``repro.codesign``: the same
    phase on another thread is not waited on."""
    program = hand_program() + [(60 * MS, 99 * MS, "repro.profile.collect", WORKER)]
    assert ps.main_thread(program) == MAIN
    assert readings(program)["profile_collect_ms"] == pytest.approx(15.0)


def test_readers_leave_out_what_is_not_there():
    """A cache-served run dispatches nothing: no prep, collect or program
    count; a trace without the program's spans reads nothing at all."""
    served = [s for s in hand_program()
              if s[2] not in ("repro.profile.schedule", "repro.profile.stack",
                              "repro.profile.collect", "repro.profile.dispatch")]
    r = readings(served, stats=[{"buckets": 0}, {"buckets": 0}])
    assert r["profile_synth_ms"] is not None and r["profile_key_ms"] is not None
    for k in ("profile_prep_ms", "profile_collect_ms", "profile_programs_per_answer"):
        assert r[k] is None, k
    assert all(v is None for v in readings([], stats=[]).values())
    assert ps.profile_cover([], (0, 100 * MS)) is None


def test_profile_cover():
    # main-thread phases cover 1..28 of 1..30 less 26..27, and 51..75 of 50..80
    want = (26 + 24) / (29 + 30)
    assert ps.profile_cover(hand_program(), (0, 100 * MS)) == pytest.approx(want)


def test_innermost_pieces():
    spans = [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d"), (12, 15, "e")]
    assert ps.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "d"), (8, 10, "a"), (12, 15, "e"),
    ]


def test_idle_by_program_span():
    idle = ps.idle_by_program_span(hand_view(), hand_program())
    # idle: 0..20, 30..70, 75..100 ms on device 0
    want = {
        "repro.codesign": 0.001,  # 0..1, before profiling opens
        "repro.profile.jobs": 0.001,
        "repro.profile.synth_wait": 0.004 + 0.004,
        "repro.profile.key": 0.003 + 0.002,
        "repro.profile.schedule": 0.002,  # 9..11: the stack opened inside it
        "repro.profile.stack": 0.003,
        "repro.profile.collect": 0.006 + 0.013,
        "repro.price": 0.010 + 0.010,
        "repro.profile": 0.001 + 0.005,  # 50..51, 75..80
        ps.OUTSIDE: 0.010 + 0.010,  # 40..50, 90..100: between answers
    }
    assert idle == pytest.approx(want)
    assert sum(idle.values()) == pytest.approx(0.020 + 0.040 + 0.025)
    assert list(idle) == sorted(idle, key=lambda k: -idle[k])


def test_recorded_trace_holds_no_program_spans():
    """The v5e trace recorded before the program had spans still reads:
    the harness's view loads, and nothing of the program is there."""
    view = trace.TraceView.from_json(json.loads((DATA / "trace_v5e_oneshot.json").read_text()))
    window = view.window()
    assert all(f([], window, 3, []) is None for f in ps.READERS.values())
    idle = ps.idle_by_program_span(view, [])
    assert list(idle) == [ps.OUTSIDE] and idle[ps.OUTSIDE] > 0


def test_tiny_cell_traced_on_the_cpu(spec, tiny):
    """A traced run of the tiny cell: every reading is there, and the run
    is still correct."""
    out = ps.run("mixtral_8x7b.oneshot", 2**33 + 5, 1.0, require_tpu=False, **tiny)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    prog = out["program"]
    assert prog["answers"] == out["attempted"] > 0
    assert prog["answer_s_traced"] > 0
    assert all(v is not None and v > 0 for v in prog["readings"].values()), prog["readings"]
    assert 0 < prog["profile_cover"] <= 1
    assert {"repro.codesign", "repro.profile", "repro.price"} <= set(prog["span_ms"])
    assert "profile_ms" in out["metrics"]
    json.dumps(out)
