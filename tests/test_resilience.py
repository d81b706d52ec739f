"""Failure taxonomy, retry/degradation ladder, fault-injection harness, and
the pipeline's recovery paths: every injector class (backend, hang,
device_loss, bitflip) drives its recovery end-to-end, recovered profiles
stay bit-exact vs the numpy oracle, and ``BatchStats.failure_report``
accounts for every injected fault with a typed cause + action."""

import concurrent.futures

import numpy as np
import pytest

from repro.core.pipeline import ProfileJob, run_profile_batch
from repro.core.switching import clear_profile_cache, profile_cache_info
from repro.kernels.activity_profile.ref import profile_gemm_toggles_ref
from repro.runtime import faults
from repro.runtime.resilience import (
    BackendCompileError,
    CacheCorruptionError,
    ContractViolationError,
    DeviceDispatchError,
    DeviceLossError,
    FailureReport,
    ProfileError,
    ProfileTimeoutError,
    RetryPolicy,
    call_with_retry,
    classify_exception,
    degradation_ladder,
)

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _pin_faults():
    """Exact-report tests must see ONLY their own injected faults: shield
    them from env-armed chaos injection (the chaos CI job sets
    $REPRO_FAULTS suite-wide)."""
    with faults.injected([]):
        yield


def _rand_gemm(m, k, n, lo=-500, hi=500):
    return (
        RNG.integers(lo, hi, size=(m, k)),
        RNG.integers(lo, hi, size=(k, n)),
    )


def _counts(p):
    return (
        round(p.a_h * p.h_transitions * p.b_h),
        round(p.a_v * p.v_transitions * p.b_v),
        p.h_transitions,
        p.v_transitions,
    )


def _jobs(n=3, dataflow="WS"):
    shapes = [(33, 20, 10), (16, 12, 8), (48, 24, 16)]
    return [
        ProfileJob(
            rows=8, cols=8, b_h=16, b_v=37, a=a, w=w,
            name=f"j{i}", dataflow=dataflow,
        )
        for i, (m, k, n_) in enumerate(shapes[:n])
        for a, w in [_rand_gemm(m, k, n_)]
    ]


def _assert_bit_exact(jobs, profiles):
    for job, p in zip(jobs, profiles):
        ref = profile_gemm_toggles_ref(
            job.a, job.w, job.rows, job.cols, job.b_h, job.b_v,
            dataflow=job.dataflow,
        )
        assert _counts(p) == ref, job.name


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


def test_classify_exception_taxonomy():
    assert isinstance(classify_exception(TimeoutError("t")), ProfileTimeoutError)
    assert isinstance(
        classify_exception(concurrent.futures.TimeoutError()), ProfileTimeoutError
    )
    assert isinstance(classify_exception(ValueError("v")), ContractViolationError)
    assert isinstance(classify_exception(ImportError("m")), BackendCompileError)
    assert isinstance(
        classify_exception(RuntimeError("pallas lowering failed")),
        BackendCompileError,
    )
    assert isinstance(
        classify_exception(RuntimeError("transfer aborted")), DeviceDispatchError
    )
    # idempotent: typed errors pass through, annotating job/stage
    err = DeviceLossError("gone")
    assert classify_exception(err, job="j1", stage="dispatch") is err
    assert err.job == "j1" and err.stage == "dispatch"
    assert err.kind == "device-loss"
    assert isinstance(err, DeviceDispatchError)  # loss subclasses dispatch
    # pre-taxonomy ValueError handlers keep catching contract violations
    assert isinstance(ContractViolationError("bad"), ValueError)
    assert "device-loss" in err.describe()


def test_degradation_ladder_rungs():
    assert degradation_ladder() == ("pallas", "xla", "numpy")
    assert degradation_ladder("auto") == ("pallas", "xla", "numpy")
    assert degradation_ladder("xla") == ("xla", "numpy")
    assert degradation_ladder("pallas")[-1] == "numpy"


def test_failure_report_accounting():
    rep = FailureReport()
    assert not rep and len(rep) == 0
    rep.add(BackendCompileError("x", job="a"), action="degraded:xla")
    rep.add(ProfileTimeoutError("y", job="b"), action="skipped")
    rep.add(BackendCompileError("z", job="b"), action="degraded:numpy")
    assert rep and len(rep) == 3
    assert rep.counts() == {"backend-compile": 2, "timeout": 1}
    assert rep.actions() == {
        "degraded:xla": 1,
        "skipped": 1,
        "degraded:numpy": 1,
    }
    assert [r.action for r in rep.for_job("b")] == ["skipped", "degraded:numpy"]
    assert "3 failures" in rep.summary()
    d = rep.as_dict()
    assert len(d["records"]) == 3 and d["counts"]["backend-compile"] == 2


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------


def test_retry_policy_deterministic_backoff():
    pol = RetryPolicy(base_delay_s=0.1, multiplier=2.0, jitter=0.5, seed=42)
    d0, d1 = pol.delay(0, "site"), pol.delay(1, "site")
    assert pol.delay(0, "site") == d0  # pure function of (seed, key, attempt)
    assert 0.1 <= d0 <= 0.15 and 0.2 <= d1 <= 0.3
    assert pol.delay(0, "other") != d0  # distinct sites decorrelate
    assert pol.delay(10, "site") <= pol.max_delay_s * (1 + pol.jitter)


def test_call_with_retry_recovers_transient_fault():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise DeviceLossError("transient")
        return "ok"

    out, attempts, last = call_with_retry(
        flaky, policy=RetryPolicy(max_attempts=3), key="k", sleep=sleeps.append
    )
    assert out == "ok" and attempts == 3
    assert last is not None and last.kind == "device-loss"
    assert len(sleeps) == 2 and sleeps[1] > sleeps[0]


def test_call_with_retry_exhaustion_raises_typed():
    def dead():
        raise RuntimeError("device transfer aborted")

    with pytest.raises(DeviceDispatchError) as ei:
        call_with_retry(
            dead, policy=RetryPolicy(max_attempts=2), sleep=lambda s: None
        )
    assert ei.value.attempts == 2


def test_call_with_retry_never_retries_contract_violations():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("bad shapes")

    with pytest.raises(ContractViolationError):
        call_with_retry(bad, policy=RetryPolicy(max_attempts=5), sleep=lambda s: None)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------


def test_fault_injector_deterministic_and_scoped():
    spec = [faults.FaultSpec("backend", rate=0.5)]
    fires = []
    for _ in range(2):  # identical schedule on replay
        inj = faults.FaultInjector(spec, seed=9)
        seq = []
        for i in range(20):
            try:
                inj.maybe_fail_backend("site", f"k{i}")
                seq.append(0)
            except BackendCompileError:
                seq.append(1)
        fires.append(seq)
    assert fires[0] == fires[1]
    assert 0 < sum(fires[0]) < 20  # rate=0.5 actually splits

    # match pins a fault to one site; max_fires caps it
    inj = faults.FaultInjector(
        [faults.FaultSpec("device_loss", match="d1", max_fires=1)]
    )
    inj.maybe_lose_device("shard", "d0")  # no match: silent
    with pytest.raises(DeviceLossError):
        inj.maybe_lose_device("shard", "d1")
    inj.maybe_lose_device("shard", "d1")  # capped: silent
    assert inj.fired_kinds() == {"device_loss"}
    assert [f.site for f in inj.fired] == ["shard"]


def test_fault_injector_bitflip_is_single_deterministic_bit():
    inj = faults.FaultInjector([faults.FaultSpec("bitflip")], seed=5)
    raw = b"hello profile store"
    out = inj.maybe_corrupt(raw, "store-read", "k")
    assert out != raw and len(out) == len(raw)
    diff = [i for i, (x, y) in enumerate(zip(raw, out)) if x != y]
    assert len(diff) == 1
    assert bin(raw[diff[0]] ^ out[diff[0]]).count("1") == 1
    inj2 = faults.FaultInjector([faults.FaultSpec("bitflip")], seed=5)
    assert inj2.maybe_corrupt(raw, "store-read", "k") == out


def test_fault_env_activation(monkeypatch):
    faults.clear()
    monkeypatch.setenv("REPRO_FAULTS", "backend=0.25,hang=1,seed=3,hang_s=0.01")
    inj = faults.active()
    assert inj is not None and inj.seed == 3 and inj.hang_s == 0.01
    assert {s.kind for s in inj.specs} == {"backend", "hang"}
    faults.clear()
    monkeypatch.setenv("REPRO_FAULTS", "")
    assert faults.active() is None
    monkeypatch.setenv("REPRO_FAULTS", "warp=1")
    faults.clear()
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.active()
    faults.clear()


# ---------------------------------------------------------------------------
# pipeline recovery paths (XLA rendering: runs on CPU CI)
# ---------------------------------------------------------------------------


def test_ladder_lands_on_numpy_bit_exact():
    """Fused dispatch AND both device rungs fail -> numpy, bit-exact."""
    jobs = _jobs()
    specs = [
        faults.FaultSpec("backend", match="bucket-dispatch"),
        faults.FaultSpec("backend", match="ladder:pallas"),
        faults.FaultSpec("backend", match="ladder:xla"),
    ]
    with faults.injected(specs, seed=1) as inj:
        profiles, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="degrade"
        )
    assert all(p is not None for p in profiles)
    _assert_bit_exact(jobs, profiles)
    assert stats.degraded == len(jobs) and stats.skipped == 0
    rep = stats.failure_report
    assert rep.actions() == {"degraded:numpy": len(jobs)}
    assert set(rep.counts()) == {"backend-compile"}
    assert "backend" in inj.fired_kinds()
    # engine="xla" ladder never visits the pallas rung
    assert not any(f.site == "ladder:pallas" for f in inj.fired)


def test_ladder_first_rung_recovers_without_numpy():
    """Only the fused batched dispatch fails -> first ladder rung lands."""
    jobs = _jobs(2)
    with faults.injected([faults.FaultSpec("backend", match="bucket-dispatch")]):
        profiles, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="degrade"
        )
    _assert_bit_exact(jobs, profiles)
    assert stats.failure_report.actions() == {"degraded:xla": len(jobs)}


def test_transient_fault_retried_within_rung():
    """One injected device loss at the first rung -> retry succeeds there."""
    jobs = _jobs(1)
    specs = [
        faults.FaultSpec("backend", match="bucket-dispatch"),
        faults.FaultSpec("device_loss", match="ladder:xla", max_fires=1),
    ]
    with faults.injected(specs):
        profiles, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="degrade",
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.001),
        )
    _assert_bit_exact(jobs, profiles)
    assert stats.retries == 1
    assert stats.failure_report.actions() == {"degraded:xla": 1}


def test_on_error_skip_keeps_successes():
    jobs = _jobs(3)
    with faults.injected(
        [
            faults.FaultSpec("backend", match="bucket-dispatch"),
            faults.FaultSpec("backend", match="ladder"),
        ]
    ):
        profiles, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="skip"
        )
    # all three share one bucket: the whole bucket failed, all skipped
    assert profiles == [None, None, None]
    assert stats.skipped == 3
    assert stats.failure_report.actions() == {"skipped": 3}
    # mixed outcome: only the serial-path job is poisoned, batch survives
    a1, w1 = _rand_gemm(1, 6, 4)
    degenerate = ProfileJob(  # M=1 stream: serial fallback path
        rows=8, cols=8, b_h=16, b_v=37, a=a1, w=w1, name="deg"
    )
    jobs2 = _jobs(2) + [degenerate]
    with faults.injected([faults.FaultSpec("backend", match="serial")]):
        profiles, stats = run_profile_batch(
            jobs2, use_cache=False, engine="xla", on_error="skip"
        )
    assert profiles[2] is None and stats.skipped == 1
    _assert_bit_exact(jobs2[:2], profiles[:2])
    assert stats.failure_report.for_job("deg")[0].action == "skipped"


def test_on_error_raise_is_typed_and_default():
    import os

    from repro.core.pipeline import DEFAULT_ON_ERROR

    jobs = _jobs(1)
    with faults.injected([faults.FaultSpec("backend", match="bucket-dispatch")]):
        with pytest.raises(BackendCompileError):
            run_profile_batch(
                jobs, use_cache=False, engine="xla", on_error="raise"
            )
    # the default tracks $REPRO_ON_ERROR and falls back to "raise" (the
    # chaos CI job runs this suite with the env knob set to "degrade")
    assert DEFAULT_ON_ERROR == os.environ.get("REPRO_ON_ERROR", "raise")
    with pytest.raises(ContractViolationError, match="unknown on_error"):
        run_profile_batch(jobs, use_cache=False, on_error="panic")


def test_contract_violations_raise_in_every_mode():
    a, w = _rand_gemm(10, 6, 4)
    bad = ProfileJob(
        rows=8, cols=8, b_h=16, b_v=37, make=lambda: (a, w), shape=(11, 6, 4)
    )
    for mode in ("raise", "degrade", "skip"):
        with pytest.raises(ValueError, match="declared shape"):
            run_profile_batch([bad], use_cache=False, on_error=mode)


def test_timeout_evicts_device_and_resubmits(monkeypatch):
    """A hung shard on a 2-device host: evict, resubmit once, bit-exact."""
    import jax

    from repro.runtime.health import HealthMonitor

    real = jax.local_devices()
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: real * 2)
    # 16 k_tiles x 8 n_tiles = 128 tasks -> 2 shards on 2 devices
    a, w = _rand_gemm(16, 128, 64)
    job = ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w, name="big")
    # warm the compile cache first: the cold compile runs INSIDE the timed
    # dispatch future, so on a loaded host it could trip the timeout on the
    # healthy shard too
    run_profile_batch([job], use_cache=False, engine="xla")
    health = HealthMonitor(range(2))
    with faults.injected(
        [faults.FaultSpec("hang", match="b0s1d1", max_fires=1)], hang_s=2.0
    ) as inj:
        (p,), stats = run_profile_batch(
            [job], use_cache=False, engine="xla", on_error="degrade",
            timeout_s=0.5, health=health,
        )
    assert inj.fired_kinds() == {"hang"}
    assert _counts(p) == profile_gemm_toggles_ref(a, w, 8, 8, 16, 37)
    assert stats.resubmits == 1 and stats.degraded == 0
    assert health.alive_hosts() == [0]  # device 1 was evicted
    rep = stats.failure_report
    assert rep.actions() == {"device-evicted:resubmitted": 1}
    assert rep.counts() == {"timeout": 1}


def test_device_loss_evicts_and_resubmits(monkeypatch):
    import jax

    from repro.runtime.health import HealthMonitor

    real = jax.local_devices()
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: real * 2)
    a, w = _rand_gemm(16, 128, 64)
    job = ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w)
    health = HealthMonitor(range(2))
    with faults.injected(
        [faults.FaultSpec("device_loss", match="d1", max_fires=1)]
    ):
        (p,), stats = run_profile_batch(
            [job], use_cache=False, engine="xla", on_error="degrade",
            health=health,
        )
    assert _counts(p) == profile_gemm_toggles_ref(a, w, 8, 8, 16, 37)
    assert stats.resubmits == 1
    assert stats.failure_report.counts() == {"device-loss": 1}
    assert health.alive_hosts() == [0]


def test_os_stream_bucket_failure_degrades_bit_exact():
    jobs = _jobs(2, dataflow="OS")
    with faults.injected([faults.FaultSpec("backend", match="stream-dispatch")]):
        profiles, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="degrade"
        )
    _assert_bit_exact(jobs, profiles)
    assert stats.degraded == len(jobs)
    assert stats.failure_report.actions() == {"degraded:xla": len(jobs)}


def test_recovered_profile_lands_in_cache_under_original_key():
    """Ladder recovery stores under the batched-path key: the next batch
    (no faults) serves the SAME jobs from cache without device work."""
    clear_profile_cache()
    jobs = _jobs(2)
    with faults.injected([faults.FaultSpec("backend", match="bucket-dispatch")]):
        profiles, stats = run_profile_batch(jobs, engine="xla", on_error="degrade")
    assert stats.degraded == 2
    profiles2, stats2 = run_profile_batch(jobs, engine="xla")
    assert stats2.cache_hits == 2 and stats2.degraded == 0
    assert profiles2 == profiles
    assert profile_cache_info()["hits"] >= 2
    clear_profile_cache()


def test_numpy_backend_never_touches_device_paths():
    """backend="numpy" must not trip device/bucket fault sites at all."""
    jobs = _jobs(2)
    with faults.injected(
        [
            faults.FaultSpec("backend", match="bucket"),
            faults.FaultSpec("hang", match="bucket"),
            faults.FaultSpec("device_loss"),
        ]
    ) as inj:
        profiles, stats = run_profile_batch(jobs, backend="numpy", use_cache=False)
    _assert_bit_exact(jobs, profiles)
    assert inj.fired == [] and stats.serial_fallbacks == len(jobs)


def test_failure_report_in_stats_dict():
    jobs = _jobs(1)
    with faults.injected([faults.FaultSpec("backend", match="bucket-dispatch")]):
        _, stats = run_profile_batch(
            jobs, use_cache=False, engine="xla", on_error="degrade"
        )
    d = stats.as_dict()
    assert d["degraded"] == 1
    assert d["failure_report"]["actions"] == {"degraded:xla": 1}
    assert d["failure_report"]["records"][0]["error"] == "backend-compile"
