"""Fused switching-activity engine: bit-exact equivalence vs the numpy
oracle on randomized shapes/bus widths (non-block-aligned T/R/C, negative
int16 operands), backend dispatch, the content-keyed profile cache, and the
element-weighted combine fix.

The Pallas kernel runs in interpret=True so everything executes on CPU CI;
the XLA engine is what `backend="pallas"` actually dispatches to on
non-TPU hosts and is tested across the full case matrix.
"""

import numpy as np
import pytest

from repro.core.switching import (
    ActivityProfile,
    clear_profile_cache,
    combine_profiles,
    profile_cache_info,
    profile_gemm,
)
from repro.kernels.activity_profile.kernel import STREAM_LANE_BLOCK
from repro.kernels.activity_profile.ops import (
    ToggleCounts,
    operands_fit_fused,
    profile_gemm_toggles,
    stream_toggle_total,
)
from repro.kernels.activity_profile.ref import profile_gemm_toggles_ref

RNG = np.random.default_rng(0)


def _rand_gemm(m, k, n, lo=-32767, hi=32768):
    return (
        RNG.integers(lo, hi, size=(m, k)),
        RNG.integers(lo, hi, size=(k, n)),
    )


# randomized shapes incl. non-block-aligned T/R/C and degenerate cases
CASES = [
    # m, k, n, rows, cols, b_h, b_v
    (7, 5, 3, 32, 32, 16, 37),
    (64, 64, 48, 32, 32, 16, 37),
    (100, 37, 29, 16, 8, 8, 20),
    (33, 70, 10, 32, 32, 16, 64),
    (2, 1, 1, 8, 8, 16, 37),
    (17, 16, 16, 16, 16, 32, 32),
    (257, 40, 33, 16, 16, 37, 33),  # b_h > 32: sign-extension toggles
    (1025, 96, 64, 32, 32, 16, 37),  # multiple t-blocks: boundary carry
]


@pytest.mark.parametrize("case", CASES)
def test_xla_engine_matches_oracle_bit_exact(case):
    m, k, n, rows, cols, b_h, b_v = case
    a, w = _rand_gemm(m, k, n)
    ref = profile_gemm_toggles_ref(a, w, rows, cols, b_h, b_v)
    got = profile_gemm_toggles(a, w, rows, cols, b_h, b_v, engine="xla")
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


@pytest.mark.parametrize("case", CASES[:5])
def test_pallas_kernel_matches_oracle_bit_exact(case):
    m, k, n, rows, cols, b_h, b_v = case
    a, w = _rand_gemm(m, k, n)
    ref = profile_gemm_toggles_ref(a, w, rows, cols, b_h, b_v)
    got = profile_gemm_toggles(
        a, w, rows, cols, b_h, b_v, engine="pallas", interpret=True
    )
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


def test_pallas_kernel_small_block_t_carries_across_blocks():
    # force many t-blocks so the VMEM scratch carry is exercised hard
    a, w = _rand_gemm(100, 16, 8)
    ref = profile_gemm_toggles_ref(a, w, 16, 8, 16, 37)
    got = profile_gemm_toggles(
        a, w, 16, 8, 16, 37, engine="pallas", interpret=True, block_t=8
    )
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


def test_pallas_operand_stream_splits_wide_lane_bundles():
    # more lanes than one grid cell takes: zero-padded lane blocks
    x = RNG.integers(-32767, 32768, size=(40, 2 * STREAM_LANE_BLOCK + 52))
    kw = dict(block_t=16)
    got = stream_toggle_total(x, 24, engine="pallas", interpret=True, **kw)
    assert got == stream_toggle_total(x, 24, engine="xla", **kw)


@pytest.mark.parametrize("b_v", [23, 37])
def test_pallas_tasks_split_across_calls(monkeypatch, b_v):
    """More tasks than one call's SMEM metadata holds: several calls, each
    of several lane-dense output groups, equal to the XLA rendering."""
    from repro.kernels.activity_profile import kernel
    from repro.kernels.activity_profile.batch import bucket_toggle_parts

    monkeypatch.setattr(kernel, "MAX_CALL_TASKS", 256)
    rows, cols, t_seg1, tasks = 8, 4, 9, 600
    strips = RNG.integers(-32767, 32768, size=(10, t_seg1, rows)).astype(np.int32)
    w_tiles = RNG.integers(-32767, 32768, size=(7, rows, cols)).astype(np.int32)
    ids = RNG.integers(0, 10, tasks).astype(np.int32)
    wids = RNG.integers(0, 7, tasks).astype(np.int32)
    vr = RNG.integers(0, rows + 1, tasks).astype(np.int32)
    got = kernel.activity_profile_pallas_tasks(
        strips, w_tiles, ids, wids, vr, rows=rows, cols=cols, b_v=b_v, interpret=True
    )
    _, want, _ = bucket_toggle_parts(
        strips, w_tiles, ids, wids, vr, rows=rows, cols=cols, b_h=16, b_v=b_v,
        engine="xla",
    )
    assert np.array_equal(np.asarray(got), np.asarray(want)[:tasks])


def test_fused_37bit_partial_sums_exact_at_extremes():
    """Worst-case magnitudes: +/-32767 operands, R=32 deep — 37-bit sums."""
    m, k, n = 64, 32, 8
    a = np.full((m, k), 32767, dtype=np.int64)
    a[::2] = -32767  # alternate rows: huge sign-flipping partial sums
    w = np.full((k, n), 32767, dtype=np.int64)
    w[:, ::2] = -32767
    ref = profile_gemm_toggles_ref(a, w, 32, 8, 16, 37)
    got = profile_gemm_toggles(a, w, 32, 8, 16, 37, engine="xla")
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


def test_operand_width_contract():
    a = np.full((4, 4), 40000, dtype=np.int64)
    w = np.ones((4, 4), dtype=np.int64)
    assert not operands_fit_fused(a, w)
    with pytest.raises(ValueError, match="int16-range"):
        profile_gemm_toggles(a, w, 4, 4, 16, 37, engine="xla")


def test_toggle_counts_add_and_activities():
    c = ToggleCounts(10, 20, 5, 8) + ToggleCounts(1, 2, 3, 4)
    assert c == ToggleCounts(11, 22, 8, 12)
    a_h, a_v = c.activities(b_h=2, b_v=4)
    assert a_h == 11 / (8 * 2) and a_v == 22 / (12 * 4)
    assert ToggleCounts(0, 0, 0, 0).activities(16, 37) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# backend dispatch in core.switching
# ---------------------------------------------------------------------------


def test_profile_gemm_backends_agree_exact():
    a, w = _rand_gemm(64, 64, 48, lo=-1000, hi=1000)
    pn = profile_gemm(a, w, 32, 32, 16, 37, backend="numpy", use_cache=False)
    pp = profile_gemm(a, w, 32, 32, 16, 37, backend="pallas", use_cache=False)
    assert pp.a_h == pytest.approx(pn.a_h, abs=1e-12)
    assert pp.a_v == pytest.approx(pn.a_v, abs=1e-12)
    assert (pp.h_transitions, pp.v_transitions) == (pn.h_transitions, pn.v_transitions)
    assert pp.input_zero_fraction == pn.input_zero_fraction
    assert pp.input_elements == a.size


def test_profile_gemm_backends_agree_subsampled():
    """Opt-in subsampling draws the identical plan on both backends."""
    a, w = _rand_gemm(300, 80, 70, lo=0, hi=500)
    kw = dict(max_tiles=3, max_stream=64, seed=11, use_cache=False)
    pn = profile_gemm(a, w, 32, 32, 16, 37, backend="numpy", **kw)
    pp = profile_gemm(a, w, 32, 32, 16, 37, backend="pallas", **kw)
    assert pp.a_h == pytest.approx(pn.a_h, abs=1e-12)
    assert pp.a_v == pytest.approx(pn.a_v, abs=1e-12)
    assert (pp.h_transitions, pp.v_transitions) == (pn.h_transitions, pn.v_transitions)


def test_auto_backend_falls_back_for_wide_operands():
    a = RNG.integers(-(2**30), 2**30, size=(16, 8))
    w = RNG.integers(-(2**30), 2**30, size=(8, 4))
    p = profile_gemm(a, w, 8, 8, 16, 37, use_cache=False)  # must not raise
    assert 0.0 <= p.a_v <= 1.0


def test_nonbinding_subsample_limits_are_exact():
    """max_tiles/max_stream that don't bind produce the exact profile."""
    a, w = _rand_gemm(50, 40, 20, lo=0, hi=100)
    exact = profile_gemm(a, w, 32, 32, 16, 37, use_cache=False)
    loose = profile_gemm(
        a, w, 32, 32, 16, 37, max_tiles=100, max_stream=1000, use_cache=False
    )
    assert loose == exact


# ---------------------------------------------------------------------------
# content-keyed profile cache
# ---------------------------------------------------------------------------


def test_profile_cache_hits_on_identical_content():
    clear_profile_cache()
    a, w = _rand_gemm(32, 16, 8, lo=0, hi=100)
    p1 = profile_gemm(a, w, 16, 8, 16, 37)
    # same content in a different dtype/array must hit
    p2 = profile_gemm(a.astype(np.int32), w.copy(), 16, 8, 16, 37)
    info = profile_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1 and info["size"] == 1
    assert p1 is p2
    # exact-mode key ignores the (unused) subsample seed
    p3 = profile_gemm(a, w, 16, 8, 16, 37, seed=123)
    assert p3 is p1
    # different content misses
    a2 = a.copy()
    a2[0, 0] += 1
    profile_gemm(a2, w, 16, 8, 16, 37)
    assert profile_cache_info()["misses"] == 2
    clear_profile_cache()
    info = profile_cache_info()
    assert info["size"] == info["hits"] == info["misses"] == 0
    assert info["store_hits"] == info["evictions"] == 0
    assert info["capacity"] >= 1


def test_profile_cache_distinguishes_geometry_and_backend():
    clear_profile_cache()
    a, w = _rand_gemm(32, 16, 8, lo=0, hi=100)
    profile_gemm(a, w, 16, 8, 16, 37)
    profile_gemm(a, w, 8, 8, 16, 37)
    profile_gemm(a, w, 16, 8, 16, 40)
    assert profile_cache_info()["misses"] == 3
    # an explicit backend request must never be served the other backend's
    # cached result (oracle cross-checks would compare an object with itself)
    pn = profile_gemm(a, w, 16, 8, 16, 37, backend="numpy")
    pp = profile_gemm(a, w, 16, 8, 16, 37, backend="pallas")
    assert profile_cache_info()["misses"] == 4  # numpy missed; pallas hit entry 1
    assert pn is not pp
    clear_profile_cache()


# ---------------------------------------------------------------------------
# combine_profiles weighting fix
# ---------------------------------------------------------------------------


def test_combine_zero_fraction_weighted_by_elements():
    tiny = ActivityProfile(0.1, 0.2, 16, 37, 10, 10, 1.0, input_elements=10)
    huge = ActivityProfile(0.1, 0.2, 16, 37, 10, 10, 0.0, input_elements=990)
    c = combine_profiles([tiny, huge])
    assert c.input_zero_fraction == pytest.approx(0.01)
    assert c.input_elements == 1000


def test_combine_zero_fraction_unweighted_fallback():
    """Hand-built profiles without element counts keep the seed behavior."""
    p1 = ActivityProfile(0.1, 0.2, 16, 37, 10, 10, 1.0)
    p2 = ActivityProfile(0.1, 0.2, 16, 37, 10, 10, 0.0)
    assert combine_profiles([p1, p2]).input_zero_fraction == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Output-stationary dataflow: fused engines vs the tile-walking oracle
# ---------------------------------------------------------------------------

# ragged shapes incl. non-aligned M/K/N, degenerate K, wide buses
OS_CASES = [
    # m, k, n, rows, cols, b_h, b_v
    (7, 5, 3, 32, 32, 16, 16),
    (64, 64, 48, 32, 32, 16, 16),
    (100, 37, 29, 16, 8, 8, 8),
    (33, 70, 10, 32, 32, 16, 64),
    (1, 2, 1, 8, 8, 16, 37),
    (17, 16, 16, 16, 16, 32, 32),
    (257, 40, 33, 16, 16, 37, 33),  # b > 32: sign-extension toggles
    (12, 1025, 16, 8, 8, 16, 12),  # long K stream: multiple t-blocks
]


@pytest.mark.parametrize("case", OS_CASES)
def test_os_xla_engine_matches_oracle_bit_exact(case):
    m, k, n, rows, cols, b_h, b_v = case
    a, w = _rand_gemm(m, k, n)
    ref = profile_gemm_toggles_ref(a, w, rows, cols, b_h, b_v, dataflow="OS")
    got = profile_gemm_toggles(a, w, rows, cols, b_h, b_v, dataflow="OS", engine="xla")
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


@pytest.mark.parametrize("case", OS_CASES[:5])
def test_os_pallas_kernel_matches_oracle_bit_exact(case):
    m, k, n, rows, cols, b_h, b_v = case
    a, w = _rand_gemm(m, k, n)
    ref = profile_gemm_toggles_ref(a, w, rows, cols, b_h, b_v, dataflow="OS")
    got = profile_gemm_toggles(
        a, w, rows, cols, b_h, b_v, dataflow="OS", engine="pallas", interpret=True
    )
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


def test_os_pallas_small_block_t_carries_across_blocks():
    a, w = _rand_gemm(10, 100, 8)  # K = 100 stream, many 8-step blocks
    ref = profile_gemm_toggles_ref(a, w, 8, 8, 16, 16, dataflow="OS")
    got = profile_gemm_toggles(
        a, w, 8, 8, 16, 16, dataflow="OS", engine="pallas", interpret=True, block_t=8
    )
    assert (got.h_toggles, got.v_toggles, got.h_transitions, got.v_transitions) == ref


def test_os_profile_gemm_backends_agree_exact():
    a, w = _rand_gemm(33, 70, 10, lo=-1000, hi=1000)
    pn = profile_gemm(a, w, 16, 8, 16, 16, dataflow="OS", backend="numpy", use_cache=False)
    pp = profile_gemm(a, w, 16, 8, 16, 16, dataflow="OS", backend="pallas", use_cache=False)
    assert pp.a_h == pytest.approx(pn.a_h, abs=1e-12)
    assert pp.a_v == pytest.approx(pn.a_v, abs=1e-12)
    assert (pp.h_transitions, pp.v_transitions) == (pn.h_transitions, pn.v_transitions)


def test_os_auto_backend_falls_back_for_wide_operands():
    a = RNG.integers(-(2**30), 2**30, size=(16, 8))
    w = RNG.integers(-(2**30), 2**30, size=(8, 4))
    with pytest.warns(RuntimeWarning):
        p = profile_gemm(a, w, 8, 8, 16, 16, dataflow="OS", use_cache=False)
    assert 0.0 <= p.a_v <= 1.0


# ---------------------------------------------------------------------------
# WS bit-for-bit regression: counts captured BEFORE the dataflow refactor
# ---------------------------------------------------------------------------

# profile_gemm_toggles(engine="xla") outputs on rng(42) operands, recorded
# from the pre-refactor engine — the dataflow dispatch must not perturb a
# single WS toggle.
WS_GOLDEN = {
    (64, 64, 48, 32, 32, 16, 37): (64626, 3555919, 8064, 193536),
    (33, 70, 10, 16, 8, 16, 37): (35552, 413326, 4480, 22400),
    (100, 37, 29, 16, 8, 8, 20): (58320, 1054295, 14652, 106227),
}


def test_ws_counts_unchanged_by_dataflow_refactor():
    rng = np.random.default_rng(42)
    for (m, k, n, rows, cols, b_h, b_v), want in WS_GOLDEN.items():
        a = rng.integers(-1000, 1000, size=(m, k))
        w = rng.integers(-1000, 1000, size=(k, n))
        t = profile_gemm_toggles(a, w, rows, cols, b_h, b_v, engine="xla")
        assert (t.h_toggles, t.v_toggles, t.h_transitions, t.v_transitions) == want
