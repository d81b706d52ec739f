"""Batched network-level profiling pipeline: bit-exact equivalence of the
batched engine vs the per-GEMM engine and the numpy counts oracle on ragged
job sets, cache-hit accounting across a batch, geometry-sweep pass reuse,
device sharding, serial fallbacks, and the workload-level profile_network
wrapper, and operand recipes (one synthesis per recipe, cache hits keyed
from the recipe memo). The Pallas task kernel runs under interpret=True for
CPU CI."""

import dataclasses

import numpy as np
import pytest

from repro.core.pipeline import BatchStats, ProfileJob, run_profile_batch
from repro.core.switching import (
    clear_profile_cache,
    profile_cache_info,
    profile_gemm,
    profile_gemms,
    set_profile_cache_capacity,
)
from repro.core.workloads import (
    ConvLayer,
    Gemm,
    conv_layer_job,
    gemm_job,
    profile_network,
)
from repro.kernels.activity_profile.ref import profile_gemm_toggles_ref
from repro.runtime.resilience import CacheThrashWarning

RNG = np.random.default_rng(0)


def _rand_gemm(m, k, n, lo=-32767, hi=32768):
    return (
        RNG.integers(lo, hi, size=(m, k)),
        RNG.integers(lo, hi, size=(k, n)),
    )


def _counts(p):
    """Exact integer toggle totals back out of a profile (lossless: the
    activities are integer ratios held in float64 far below 2^53)."""
    return (
        round(p.a_h * p.h_transitions * p.b_h),
        round(p.a_v * p.v_transitions * p.b_v),
        p.h_transitions,
        p.v_transitions,
    )


# Ragged multi-job batch: mixed M/K/N, non-aligned shapes, several
# geometries and bus widths, negative operands — one pipeline call.
RAGGED = [
    # m, k, n, rows, cols, b_h, b_v
    (7, 5, 3, 16, 8, 16, 37),
    (33, 70, 10, 16, 8, 16, 37),
    (100, 37, 29, 16, 8, 8, 20),
    (64, 64, 48, 32, 32, 16, 37),
    (257, 40, 33, 16, 16, 37, 33),
    (300, 80, 70, 32, 32, 16, 64),
    (50, 24, 16, 8, 8, 8, 23),  # b_v <= 32: lo-plane fast path
]


@pytest.mark.parametrize("engine,interpret", [("xla", False), ("pallas", True)])
def test_batched_ragged_set_bit_exact(engine, interpret):
    jobs = [
        ProfileJob(rows=r, cols=c, b_h=bh, b_v=bv, a=a, w=w, name=f"{m}x{k}x{n}")
        for (m, k, n, r, c, bh, bv) in RAGGED
        for a, w in [_rand_gemm(m, k, n)]
    ]
    profiles, stats = run_profile_batch(
        jobs, use_cache=False, engine=engine, interpret=interpret
    )
    assert stats.jobs == len(jobs) and stats.serial_fallbacks == 0
    for job, p in zip(jobs, profiles):
        ref = profile_gemm_toggles_ref(
            job.a, job.w, job.rows, job.cols, job.b_h, job.b_v
        )
        assert _counts(p) == ref, job.name
        s = profile_gemm(
            job.a, job.w, job.rows, job.cols, job.b_h, job.b_v,
            backend="pallas", use_cache=False,
        )
        assert (p.a_h, p.a_v) == (s.a_h, s.a_v), job.name
        assert p.input_zero_fraction == s.input_zero_fraction
        assert p.input_elements == job.a.size


def test_batched_matches_serial_on_long_streams():
    """Multi-segment streams (m >> t_seg) exercise the seeded-window splits."""
    a, w = _rand_gemm(1025, 96, 64)
    (p,), _ = run_profile_batch(
        [ProfileJob(rows=32, cols=32, b_h=16, b_v=37, a=a, w=w)], use_cache=False
    )
    s = profile_gemm(a, w, 32, 32, 16, 37, backend="pallas", use_cache=False)
    assert _counts(p) == _counts(s)


def test_geometry_sweep_shares_one_pass():
    """One GEMM profiled across several (rows, cols): the h-strip totals and
    the rows-dependent v pass are computed once and shared (cols only
    rescales ceil(N/cols)); profiles stay bit-exact vs per-GEMM calls."""
    a, w = _rand_gemm(50, 40, 20, lo=-500, hi=500)
    jobs = [
        ProfileJob(rows=32, cols=c, b_h=16, b_v=37, a=a, w=w) for c in (32, 16, 8)
    ]
    profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.passes == 1 and stats.pass_reuse == 2
    for c, p in zip((32, 16, 8), profiles):
        s = profile_gemm(a, w, 32, c, 16, 37, backend="pallas", use_cache=False)
        assert _counts(p) == _counts(s)
    # different rows => new v pass required
    jobs.append(ProfileJob(rows=16, cols=32, b_h=16, b_v=37, a=a, w=w))
    _, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.passes == 2 and stats.pass_reuse == 2


def test_shape_aliased_operands_do_not_share_a_pass():
    """Same bytes reshaped to different (M, K)/(K, N) are different streams:
    the pass key must include shapes, not just content digests."""
    buf_a = RNG.integers(-50, 50, size=64)
    buf_w = RNG.integers(-50, 50, size=64)
    jobs = [
        ProfileJob(rows=8, cols=8, b_h=16, b_v=37,
                   a=buf_a.reshape(8, 8), w=buf_w.reshape(8, 8)),
        ProfileJob(rows=8, cols=8, b_h=16, b_v=37,
                   a=buf_a.reshape(4, 16), w=buf_w.reshape(16, 4)),
    ]
    profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.passes == 2 and stats.pass_reuse == 0
    for job, p in zip(jobs, profiles):
        assert _counts(p) == profile_gemm_toggles_ref(
            job.a, job.w, 8, 8, 16, 37
        )


def test_intra_batch_dedup_and_cache_accounting():
    clear_profile_cache()
    a, w = _rand_gemm(32, 16, 8, lo=0, hi=100)
    jobs = [
        ProfileJob(rows=16, cols=8, b_h=16, b_v=37, a=a, w=w),
        # same content, different dtype/copy: must dedup to one device pass
        ProfileJob(rows=16, cols=8, b_h=16, b_v=37, a=a.astype(np.int32), w=w.copy()),
    ]
    profiles, stats = run_profile_batch(jobs)
    assert stats.passes == 1 and stats.pass_reuse == 1 and stats.cache_hits == 0
    assert _counts(profiles[0]) == _counts(profiles[1])
    # second batch: every job is a content-cache hit, nothing runs on device
    profiles2, stats2 = run_profile_batch(jobs)
    assert stats2.cache_hits == 2 and stats2.passes == 0 and stats2.buckets == 0
    assert profiles2[0] == profiles[0]
    # the cache is shared with the serial API (same keys)
    hits_before = profile_cache_info()["hits"]
    profile_gemm(a, w, 16, 8, 16, 37)
    assert profile_cache_info()["hits"] == hits_before + 1
    clear_profile_cache()


def test_serial_fallbacks_and_degenerate_shapes():
    wide_a = RNG.integers(-(2**30), 2**30, size=(16, 8))
    wide_w = RNG.integers(-(2**30), 2**30, size=(8, 4))
    tiny_a, tiny_w = _rand_gemm(1, 4, 4)  # m < 2: zero transitions
    a, w = _rand_gemm(20, 8, 4, lo=0, hi=50)
    jobs = [
        ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=wide_a, w=wide_w),
        ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=tiny_a, w=tiny_w),
        ProfileJob(rows=8, cols=4, b_h=16, b_v=37, a=a, w=w),
    ]
    with pytest.warns(RuntimeWarning):
        profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.serial_fallbacks == 2 and stats.passes == 1
    s_wide = profile_gemm(wide_a, wide_w, 8, 8, 16, 37, backend="numpy",
                             use_cache=False)
    assert profiles[0] == s_wide
    assert profiles[1].h_transitions == 0 and profiles[1].a_v == 0.0
    assert _counts(profiles[2]) == profile_gemm_toggles_ref(a, w, 8, 4, 16, 37)


def test_backend_numpy_runs_serial_oracle():
    a, w = _rand_gemm(12, 6, 5, lo=0, hi=50)
    jobs = [ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w)]
    profiles, stats = run_profile_batch(jobs, backend="numpy", use_cache=False)
    assert stats.serial_fallbacks == 1 and stats.buckets == 0
    assert _counts(profiles[0]) == profile_gemm_toggles_ref(a, w, 8, 8, 16, 37)


def test_device_sharding_bit_exact(monkeypatch):
    """Simulated multi-device host: task-axis shards stay bit-exact."""
    import jax

    real = jax.local_devices()
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: real * 2)
    a, w = _rand_gemm(300, 80, 70)
    (p,), _ = run_profile_batch(
        [ProfileJob(rows=32, cols=32, b_h=16, b_v=37, a=a, w=w)], use_cache=False
    )
    assert _counts(p) == profile_gemm_toggles_ref(a, w, 32, 32, 16, 37)


def test_lazy_jobs_and_shape_validation():
    a, w = _rand_gemm(10, 6, 4, lo=0, hi=50)
    job = ProfileJob(
        rows=8, cols=8, b_h=16, b_v=37, make=lambda: (a, w), shape=(10, 6, 4)
    )
    (p,), _ = run_profile_batch([job], use_cache=False)
    assert _counts(p) == profile_gemm_toggles_ref(a, w, 8, 8, 16, 37)
    bad = ProfileJob(
        rows=8, cols=8, b_h=16, b_v=37, make=lambda: (a, w), shape=(11, 6, 4)
    )
    with pytest.raises(ValueError, match="declared shape"):
        run_profile_batch([bad], use_cache=False)
    with pytest.raises(ValueError, match="needs shape"):
        ProfileJob(rows=8, cols=8, b_h=16, b_v=37, make=lambda: (a, w)).gemm_shape()


def test_profile_gemms_wrapper_and_order():
    jobs = []
    expect = []
    for m, k, n in [(9, 5, 4), (21, 17, 3), (6, 2, 2)]:
        a, w = _rand_gemm(m, k, n, lo=-200, hi=200)
        jobs.append(ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w))
        expect.append(profile_gemm_toggles_ref(a, w, 8, 8, 16, 37))
    profiles = profile_gemms(jobs, use_cache=False)
    assert [_counts(p) for p in profiles] == expect


def test_profile_network_matches_serial_layers():
    layers = [
        ConvLayer("t1", k=1, h=5, w=5, c=40, m=9, input_density=0.5),
        ConvLayer("t2", k=3, h=3, w=3, c=7, m=17, input_density=0.4),
    ]
    clear_profile_cache()
    batched, stats = profile_network(
        layers, rows=16, cols=8, bits=8, use_cache=False, return_stats=True
    )
    assert isinstance(stats, BatchStats) and stats.jobs == 2
    for i, layer in enumerate(layers):
        job = conv_layer_job(layer, rows=16, cols=8, bits=8, seed=i)
        a, w = job.operands()
        assert _counts(batched[i]) == profile_gemm_toggles_ref(
            a, w, 16, 8, job.b_h, job.b_v
        )
    # subsampling falls back to the serial per-GEMM estimate
    sub, stats_sub = profile_network(
        layers, rows=16, cols=8, bits=8, max_tiles=1, max_stream=8,
        use_cache=False, return_stats=True,
    )
    assert stats_sub.serial_fallbacks == 2
    assert all(0.0 <= p.a_v <= 1.0 for p in sub)


# ---------------------------------------------------------------------------
# Output-stationary jobs: stream buckets, geometry-free pass reuse
# ---------------------------------------------------------------------------

OS_RAGGED = [
    # m, k, n, rows, cols, b_h, b_v
    (7, 5, 3, 16, 8, 16, 16),
    (33, 70, 10, 16, 8, 16, 12),
    (100, 37, 29, 16, 8, 8, 8),
    (257, 40, 33, 16, 16, 37, 33),
    (12, 300, 16, 8, 8, 16, 16),  # long K: multi-segment stream windows
]


@pytest.mark.parametrize("engine,interpret", [("xla", False), ("pallas", True)])
def test_batched_os_ragged_set_bit_exact(engine, interpret):
    jobs = [
        ProfileJob(
            rows=r, cols=c, b_h=bh, b_v=bv, a=a, w=w,
            dataflow="OS", name=f"os{m}x{k}x{n}",
        )
        for (m, k, n, r, c, bh, bv) in OS_RAGGED
        for a, w in [_rand_gemm(m, k, n)]
    ]
    profiles, stats = run_profile_batch(
        jobs, use_cache=False, engine=engine, interpret=interpret
    )
    assert stats.serial_fallbacks == 0 and stats.tasks == 0
    for job, p in zip(jobs, profiles):
        ref = profile_gemm_toggles_ref(
            job.a, job.w, job.rows, job.cols, job.b_h, job.b_v, dataflow="OS"
        )
        assert _counts(p) == ref, job.name
        s = profile_gemm(
            job.a, job.w, job.rows, job.cols, job.b_h, job.b_v,
            dataflow="OS", backend="pallas", use_cache=False,
        )
        assert (p.a_h, p.a_v) == (s.a_h, s.a_v), job.name


def test_mixed_ws_os_batch_bit_exact():
    a, w = _rand_gemm(50, 40, 20, lo=-500, hi=500)
    jobs = [
        ProfileJob(rows=16, cols=8, b_h=16, b_v=37, a=a, w=w, dataflow="WS"),
        ProfileJob(rows=16, cols=8, b_h=16, b_v=16, a=a, w=w, dataflow="OS"),
    ]
    profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.serial_fallbacks == 0
    for job, p in zip(jobs, profiles):
        assert _counts(p) == profile_gemm_toggles_ref(
            a, w, job.rows, job.cols, job.b_h, job.b_v, dataflow=job.dataflow
        ), job.dataflow


def test_os_geometry_sweep_shares_stream_passes():
    """OS stream passes carry no geometry: one A pass + one W pass serve
    every (rows, cols) combination, bit-exact against per-GEMM calls."""
    a, w = _rand_gemm(50, 40, 20, lo=-500, hi=500)
    geoms = [(32, 32), (16, 8), (8, 4)]
    jobs = [
        ProfileJob(rows=r, cols=c, b_h=16, b_v=16, a=a, w=w, dataflow="OS")
        for (r, c) in geoms
    ]
    profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.passes == 2 and stats.pass_reuse == 2 * (len(geoms) - 1)
    for (r, c), p in zip(geoms, profiles):
        assert _counts(p) == profile_gemm_toggles_ref(
            a, w, r, c, 16, 16, dataflow="OS"
        )
    # different bus width => the affected stream re-profiles, the other reuses
    jobs.append(ProfileJob(rows=32, cols=32, b_h=16, b_v=12, a=a, w=w, dataflow="OS"))
    _, stats2 = run_profile_batch(jobs, use_cache=False)
    assert stats2.passes == 3  # A@16 + W@16 + W@12


def test_os_degenerate_and_serial_fallbacks():
    tiny_a, tiny_w = _rand_gemm(4, 1, 4)  # K < 2: zero transitions
    wide_a = RNG.integers(-(2**30), 2**30, size=(6, 8))
    wide_w = RNG.integers(-(2**30), 2**30, size=(8, 4))
    a, w = _rand_gemm(10, 12, 6, lo=0, hi=50)
    jobs = [
        ProfileJob(rows=4, cols=4, b_h=16, b_v=16, a=tiny_a, w=tiny_w, dataflow="OS"),
        ProfileJob(rows=4, cols=4, b_h=16, b_v=16, a=wide_a, w=wide_w, dataflow="OS"),
        ProfileJob(rows=4, cols=4, b_h=16, b_v=16, a=a, w=w, dataflow="OS"),
    ]
    with pytest.warns(RuntimeWarning):
        profiles, stats = run_profile_batch(jobs, use_cache=False)
    assert stats.serial_fallbacks == 2
    assert profiles[0].h_transitions == 0 and profiles[0].a_v == 0.0
    assert _counts(profiles[1]) == profile_gemm_toggles_ref(
        wide_a, wide_w, 4, 4, 16, 16, dataflow="OS"
    )
    assert _counts(profiles[2]) == profile_gemm_toggles_ref(
        a, w, 4, 4, 16, 16, dataflow="OS"
    )


def test_os_cache_roundtrip_and_dataflow_isolation():
    clear_profile_cache()
    a, w = _rand_gemm(16, 12, 8, lo=0, hi=100)
    ws_job = ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w)
    os_job = ProfileJob(rows=8, cols=8, b_h=16, b_v=37, a=a, w=w, dataflow="OS")
    profiles, stats = run_profile_batch([ws_job, os_job])
    assert stats.cache_hits == 0
    # same operands+geometry, different dataflow: distinct cache entries
    profiles2, stats2 = run_profile_batch([ws_job, os_job])
    assert stats2.cache_hits == 2 and stats2.passes == 0
    assert profiles2[0] == profiles[0] and profiles2[1] == profiles[1]
    assert profiles[0].a_v != profiles[1].a_v
    # the cache is shared with the serial API (same v3 keys)
    hits = profile_cache_info()["hits"]
    profile_gemm(a, w, 8, 8, 16, 37, dataflow="OS")
    assert profile_cache_info()["hits"] == hits + 1
    clear_profile_cache()


def test_os_profile_network_matches_serial_layers():
    layers = [
        ConvLayer("t1", k=1, h=5, w=5, c=40, m=9, input_density=0.5),
        ConvLayer("t2", k=3, h=3, w=3, c=7, m=17, input_density=0.4),
    ]
    batched, stats = profile_network(
        layers, rows=16, cols=8, bits=8, dataflow="OS",
        use_cache=False, return_stats=True,
    )
    assert isinstance(stats, BatchStats) and stats.jobs == 2
    for i, layer in enumerate(layers):
        job = conv_layer_job(layer, rows=16, cols=8, bits=8, seed=i, dataflow="OS")
        a, w = job.operands()
        assert job.b_v == 8  # OS default: operand width, not accumulator width
        assert _counts(batched[i]) == profile_gemm_toggles_ref(
            a, w, 16, 8, job.b_h, job.b_v, dataflow="OS"
        )


# ---------------------------------------------------------------------------
# Operand recipes: one synthesis per recipe, memo-keyed cache hits
# ---------------------------------------------------------------------------

RECIPE_BASE = dict(m=24, k=40, n=16, density=0.5, seed=5, bits=8)


def _recipe_job(rows=8, cols=8, dataflow="WS", **over):
    p = {**RECIPE_BASE, **over}
    return gemm_job(
        Gemm("g", p["m"], p["k"], p["n"]), rows=rows, cols=cols, bits=p["bits"],
        seed=p["seed"], density=p["density"], clip=None, dataflow=dataflow,
    )


@pytest.mark.parametrize(
    "field,value",
    [("m", 25), ("k", 41), ("n", 17), ("density", 0.6), ("seed", 6), ("bits", 6)],
)
def test_gemm_recipe_fixes_the_operands(field, value):
    """Equal recipes build byte-equal operands whatever the geometry and
    dataflow; changing any one recipe field changes them."""
    base = _recipe_job()
    twin = _recipe_job(rows=16, cols=4, dataflow="OS")
    assert twin.recipe == base.recipe and twin.b_v != base.b_v
    a0, w0 = base.operands()
    a1, w1 = twin.operands()
    assert a0.tobytes() == a1.tobytes() and w0.tobytes() == w1.tobytes()
    changed = _recipe_job(**{field: value})
    assert changed.recipe != base.recipe
    a2, w2 = changed.operands()
    assert (a2.shape, w2.shape) != (a0.shape, w0.shape) or not (
        np.array_equal(a2, a0) and np.array_equal(w2, w0)
    )


# three activity classes (two WS depths and OS) x three operand recipes
RECIPE_CLASSES = [(8, "WS"), (16, "WS"), (8, "OS")]
RECIPE_SHAPES = [dict(m=24, k=40, n=16), dict(m=9, k=33, n=20, density=None), dict(m=40, k=16, n=8, seed=2)]


def _recipe_batch(make_calls=None):
    """Class-major recipe jobs, as ``design_gemm_jobs`` emits them; each
    ``make`` call is appended to ``make_calls`` when given."""
    jobs = [
        _recipe_job(rows=rows, dataflow=df, **shape)
        for rows, df in RECIPE_CLASSES
        for shape in RECIPE_SHAPES
    ]
    if make_calls is not None:
        for job in jobs:
            job.make = _counted(job.make, job.recipe, make_calls)
    return jobs


def _counted(make, recipe, calls):
    def counted_make():
        calls.append(recipe)
        return make()

    return counted_make


def test_recipe_batch_synthesizes_each_recipe_once():
    clear_profile_cache()
    calls = []
    jobs = _recipe_batch(calls)
    profiles, stats = run_profile_batch(jobs)
    assert stats.jobs == 9 and stats.cache_hits == 0 and stats.recipe_hits == 0
    assert stats.synthesized == len(RECIPE_SHAPES) == len(calls) == len(set(calls))
    for job, p in zip(_recipe_batch(), profiles):
        a, w = job.operands()
        s = profile_gemm(
            a, w, job.rows, job.cols, job.b_h, job.b_v,
            dataflow=job.dataflow, backend="pallas", use_cache=False,
        )
        assert _counts(p) == _counts(s) == profile_gemm_toggles_ref(
            a, w, job.rows, job.cols, job.b_h, job.b_v, dataflow=job.dataflow
        )
        assert (p.input_zero_fraction, p.input_elements) == (
            s.input_zero_fraction, s.input_elements
        )
    clear_profile_cache()


def test_warm_recipe_batch_synthesizes_nothing():
    clear_profile_cache()
    first, _ = run_profile_batch(_recipe_batch())
    calls = []
    profiles, stats = run_profile_batch(_recipe_batch(calls))
    assert calls == [] and stats.synthesized == 0
    assert stats.recipe_hits == stats.cache_hits == stats.jobs == 9
    assert stats.passes == stats.buckets == 0
    assert profiles == first
    clear_profile_cache()


def test_clearing_the_cache_empties_the_recipe_memo():
    clear_profile_cache()
    run_profile_batch(_recipe_batch())
    clear_profile_cache()
    calls = []
    _, stats = run_profile_batch(_recipe_batch(calls))
    assert stats.recipe_hits == stats.cache_hits == 0
    assert stats.synthesized == len(calls) == len(RECIPE_SHAPES)
    clear_profile_cache()


def test_recipe_memo_bounded_by_the_cache_capacity():
    from repro.core import switching

    clear_profile_cache()
    prev = set_profile_cache_capacity(2)
    try:
        with pytest.warns(CacheThrashWarning):
            run_profile_batch(_recipe_batch())
        assert len(switching._RECIPE_FACTS) == 2
        # the memo keys the two newest recipes' jobs without operands; the
        # evicted profiles still miss and are profiled again, bit-exact
        calls = []
        profiles, stats = run_profile_batch(_recipe_batch(calls))
        assert stats.recipe_hits == 2 * len(RECIPE_CLASSES)
        assert stats.cache_hits == 2 and stats.synthesized == len(set(calls))
        for job, p in zip(_recipe_batch(), profiles):
            assert _counts(p) == profile_gemm_toggles_ref(
                *job.operands(), job.rows, job.cols, job.b_h, job.b_v,
                dataflow=job.dataflow,
            )
    finally:
        set_profile_cache_capacity(prev)
        clear_profile_cache()


@pytest.mark.parametrize("kind", ["conv", "eager", "stripped"])
def test_jobs_without_a_recipe_unchanged(kind):
    """Jobs without a recipe (conv layers, eager operands, recipe jobs with
    the recipe taken off) are synthesized and keyed per job, as before the
    memo: the same scheduler counts and profiles as their recipe twins."""
    if kind == "conv":
        layers = [
            ConvLayer("t1", k=1, h=5, w=5, c=40, m=9, input_density=0.5),
            ConvLayer("t2", k=3, h=3, w=3, c=7, m=17, input_density=0.4),
        ]

        def batch():
            return [
                conv_layer_job(layer, rows=rows, cols=8, bits=8, seed=i, dataflow=df)
                for rows, df in RECIPE_CLASSES
                for i, layer in enumerate(layers)
            ]
    else:
        def batch():
            jobs = [dataclasses.replace(j, recipe=None) for j in _recipe_batch()]
            if kind == "eager":
                for job in jobs:
                    job.operands()
                    job.make = None
            return jobs

    clear_profile_cache()
    jobs = batch()
    assert all(job.recipe is None for job in jobs)
    profiles, stats = run_profile_batch(jobs)
    built = 0 if kind == "eager" else len(jobs)
    assert stats.recipe_hits == 0 and stats.synthesized == built
    for job, p in zip(batch(), profiles):
        a, w = job.operands()
        assert _counts(p) == profile_gemm_toggles_ref(
            a, w, job.rows, job.cols, job.b_h, job.b_v, dataflow=job.dataflow
        )
    warm, warm_stats = run_profile_batch(batch())
    assert warm == profiles
    assert warm_stats.cache_hits == len(jobs) and warm_stats.recipe_hits == 0
    assert warm_stats.synthesized == built  # keyed from the operands again
    if kind != "conv":
        clear_profile_cache()
        twin, twin_stats = run_profile_batch(_recipe_batch())
        assert twin == profiles
        skip = {"synthesized", "failure_report"}
        assert {k: v for k, v in twin_stats.as_dict().items() if k not in skip} == {
            k: v for k, v in stats.as_dict().items() if k not in skip
        }
    clear_profile_cache()


def test_digest_key_equals_operand_key_and_hits_the_store(tmp_path):
    """A key built from the memo's digests is the operand-built key, byte
    for byte: a store entry written by a job without a recipe is hit by its
    recipe twin."""
    from repro.core.switching import (
        _cache_key,
        _operand_facts,
        configure_profile_store,
    )

    a, w = _recipe_job().operands()
    facts = _operand_facts(a, w)
    for mode in [("pallas", "WS", "exact"), ("numpy", "OS", "exact"), ("pallas", "WS", "exact", "lanes")]:
        assert facts.cache_key(8, 4, 8, 23, mode) == _cache_key(a, w, 8, 4, 8, 23, mode)

    clear_profile_cache()
    configure_profile_store(tmp_path / "store")
    try:
        plain = dataclasses.replace(_recipe_job(), recipe=None)
        (p0,), s0 = run_profile_batch([plain])
        assert s0.cache_hits == 0 and s0.synthesized == 1
        clear_profile_cache()  # memory only: the store keeps the entry
        (p1,), s1 = run_profile_batch([_recipe_job()])
        assert s1.store_hits == s1.cache_hits == 1 and s1.synthesized == 1
        assert p1 == p0
    finally:
        configure_profile_store(None)
        clear_profile_cache()
