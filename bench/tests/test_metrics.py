"""Every reader of ``bench/metrics/`` on a hand-made trace with known
answers, and on a small trace recorded on a TPU v5e."""

import json

import pytest

from bench import cells, kernel_bytes, trace
from bench.harness import RunView
from bench.peaks import peaks_for

from .conftest import DATA

MS = 1_000_000  # ns
TASK_OP = (
    "%activity_profile_pallas_tasks.1 = s32[16,1,128] custom-call(s32[2048] %a, "
    "s32[64,17,16] %copy), custom_call_target=\"tpu_custom_call\""
)
STREAM_OP = (
    "%stream_strips_toggles_pallas.2 = s32[8,1,128] custom-call(s32[5,33,128] %s), "
    "custom_call_target=\"tpu_custom_call\""
)


def hand_view() -> trace.TraceView:
    """A 100 ms window on two chips, two answers."""
    return trace.TraceView(
        ops={
            0: [(0, 10 * MS, "%copy.1 = s32[64,17,16] copy(s32[64,17,16] %p)"),
                (5 * MS, 20 * MS, TASK_OP), (30 * MS, 40 * MS, "%fusion.3 = f32[4,40] fusion()"),
                (95 * MS, 130 * MS, STREAM_OP)],
            1: [(50 * MS, 60 * MS, "e")],
        },
        modules={
            0: [(0, 20 * MS, "jit_activity_profile_pallas_tasks(7)"),
                (30 * MS, 40 * MS, "jit__coeff_eval_core(12)"),
                (95 * MS, 130 * MS, "jit_stream_strips_toggles_pallas(3)")],
            1: [(50 * MS, 60 * MS, "jit__unknown(12)"),
                (80 * MS, 85 * MS, "jit__unknown(40)")],  # outside any price span
        },
        spans=[
            (0, 100 * MS, "bench.window"),
            (0, 4 * MS, "bench.expand:repro.serving.codesign.weighted_gemms"),
            (4 * MS, 30 * MS, "bench.profile:repro.serving.codesign.measured_design_gemm_activities"),
            (30 * MS, 70 * MS, "bench.price:repro.serving.codesign.evaluate_fleet_objective"),
            (31 * MS, 35 * MS, "bench.lower:repro.layout.power.lower_layout_coeffs"),
            (33 * MS, 38 * MS, "bench.lower:repro.core.objective.lower_partition_coeffs"),
            (70 * MS, 100 * MS, "bench.profile:repro.serving.codesign.measured_design_gemm_activities"),
        ],
    )


def hand_run(view=None, **kw) -> RunView:
    calls = [
        {"kernel": "activity_profile_pallas_tasks", "strips": (10, 9, 16), "itemsize": 4,
         "w_tiles": (4, 16, 8), "tasks": 40},
        {"kernel": "stream_strips_toggles_pallas", "strips": (5, 33, 128), "itemsize": 4},
    ]
    base = dict(
        cell="mixtral_8x7b.oneshot", answers=2, window_s=0.1, latencies=[0.05, 0.05],
        compiles=[("<lambda>", 0.01), ("<lambda>", 0.01), ("_coeff_eval_core", 1.0)],
        profile_hits=3, profile_misses=9, kernel_calls=calls, devices=2,
        trace=view if view is not None else hand_view(), peaks=peaks_for("TPU v5 lite"),
    )
    base.update(kw)
    return RunView(**base)


def read(name, run):
    return cells.metric_reader(name)(run)


def test_layer_spans():
    run = hand_run()
    assert read("expand_ms", run) == pytest.approx(4 / 2)
    assert read("profile_ms", run) == pytest.approx((26 + 30) / 2)
    # overlapping lower spans count once: 31..38 ms
    assert read("lower_ms", run) == pytest.approx(7 / 2)


def test_counters():
    run = hand_run()
    assert read("profile_cache_hit_share", run) == pytest.approx(25.0)
    assert read("compiles_per_answer", run) == pytest.approx(1.5)
    assert read("profile_cache_hit_share", hand_run(profile_hits=0, profile_misses=0)) is None


def test_device_idle_and_jop_time():
    run = hand_run()
    # chip 0 busy 0..20, 30..40, 95..100 (clipped) = 35 ms; chip 1 10 ms
    assert read("device_idle_share", run) == pytest.approx(100 * (1 - 22.5 / 100))
    # the J/op program: 10 ms on each chip, two answers
    assert read("jop_device_ms", run) == pytest.approx(10.0)


def test_kernel_hbm_share():
    run = hand_run()
    want_bytes = (10 * 9 * 16 * 4 + 4 * 16 * 8 * 4 + 4 * 4 * 40) + (5 * 33 * 128 * 4 + 4 * 5)
    assert kernel_bytes.total_bytes(run.kernel_calls) == want_bytes
    # kernel ops in the window: 5..20 ms and 95..100 ms (clipped)
    assert read("profile_kernel_hbm_share", run) == pytest.approx(
        100 * want_bytes / (20e-3 * 819e9)
    )


def test_readers_leave_out_what_is_not_there():
    empty = trace.TraceView({}, {}, [(0, 100 * MS, "bench.window")])
    run = hand_run(view=empty)
    for name in ("expand_ms", "profile_ms", "lower_ms", "device_idle_share",
                 "jop_device_ms", "profile_kernel_hbm_share"):
        assert read(name, run) is None, name
    untraced = hand_run(view=None)
    untraced.trace = None
    assert read("device_idle_share", untraced) is None
    assert read("profile_kernel_hbm_share", hand_run(kernel_calls=[])) is None


def test_breakdown_labels_idle_gaps():
    b = trace.breakdown(hand_view())
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "jit_activity_profile_pallas_tasks"
    assert dict(b["device_ops"])["jit__coeff_eval_core"] == pytest.approx(10e-3)
    assert dict(b["device_ops"])["jit__unknown"] == pytest.approx(15e-3)
    # chip 0 idle: 20..30 (profile), 40..95 (price, then profile)
    gaps = b["idle_gaps"]
    assert gaps[0][0].startswith("bench.price:") and gaps[0][1] == pytest.approx(55e-3)
    assert gaps[1][0].startswith("bench.profile:") and gaps[1][1] == pytest.approx(10e-3)


def test_trace_json_round_trip():
    v = hand_view()
    assert trace.TraceView.from_json(json.loads(json.dumps(v.to_json()))) == v


def test_host_spans_from_a_cpu_trace(tmp_path):
    """``load`` finds the harness's annotations in a real profiler trace."""
    import jax

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.expand:x"):
            jax.numpy.ones(4).block_until_ready()
    trace.stop()
    view = trace.load(str(tmp_path))
    assert [n for _, _, n in view.spans] == [trace.WINDOW_SPAN, "bench.expand:x"]
    lo, hi = view.window()
    assert hi > lo


def recorded_run() -> RunView:
    """Three oneshot answers of mixtral_8x7b traced on a TPU v5e, with the
    kernel calls read back from the operand shapes of the trace's
    custom-call ops."""
    import re

    view = trace.TraceView.from_json(json.loads((DATA / "trace_v5e_oneshot.json").read_text()))
    calls = []
    for _, _, n in view.ops[0]:
        m = re.match(r"^%(\w+?)[.\d]* = .*custom-call\((.*)\), custom_call_target", n)
        if not m:
            continue
        shapes = [tuple(int(x) for x in d.split(","))
                  for d in re.findall(r"s32\[([\d,]+)\]", m.group(2))]
        if m.group(1) == "activity_profile_pallas_tasks":
            calls.append({"kernel": m.group(1), "strips": shapes[3], "w_tiles": shapes[4],
                          "tasks": shapes[0][0], "itemsize": 4})
        elif m.group(1) == "stream_strips_toggles_pallas":
            calls.append({"kernel": m.group(1), "strips": shapes[0], "itemsize": 4})
    lo, hi = view.window()
    return hand_run(view=view, answers=3, window_s=(hi - lo) * 1e-9, devices=1,
                    kernel_calls=calls)


def test_readers_on_a_recorded_trace():
    run = recorded_run()
    lo, hi = run.trace.window()
    # the idle share by brute force: 10-microsecond bins marked busy
    step = 10_000
    bins = bytearray(int((hi - lo) // step) + 1)
    for s, e, _ in run.trace.ops[0]:
        for b in range(int((max(s, lo) - lo) // step), int((min(e, hi) - lo) // step) + 1):
            if s < hi and e > lo:
                bins[b] = 1
    idle = 100 * (1 - sum(bins) / len(bins))
    got = read("device_idle_share", run)
    assert 50 < got < 100
    assert got == pytest.approx(idle, abs=2.0)
    for name in ("expand_ms", "profile_ms", "lower_ms", "jop_device_ms"):
        assert read(name, run) > 0, name
    # profiling holds most of an oneshot answer; the J/op program is short
    assert read("profile_ms", run) > read("lower_ms", run) > read("expand_ms", run)
    assert read("jop_device_ms", run) < 1.0
    share = read("profile_kernel_hbm_share", run)
    assert 0 < share <= 100
