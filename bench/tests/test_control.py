"""The controls, at a size a test run holds, come out as not correct."""

import numpy as np

from bench import reference


def test_bf16_jop_program_is_not_correct(run_tiny):
    out = run_tiny(control="jop_bf16")
    assert not out["correct"]
    for name in ("jop_plain_gap", "jop_rel_gap"):
        c = out["checks"][name]
        assert c["value"] > c["limit"]


def test_f32_partial_sums_are_not_correct(run_tiny):
    out = run_tiny(control="counts_f32")
    assert not out["correct"]
    assert out["checks"]["count_mismatch_jobs"]["value"] > 0


def test_reference_counts_small_cases():
    # WS, one 2-row tile, one column: partial sums (1, 3) then (2, 6) -> toggles
    a = np.array([[1, 1], [2, 2]])
    w = np.array([[1], [2]])
    h, v, ht, vt = reference.toggle_counts(a, w, 2, 1, 8, 8, "WS")
    # h: a columns 1->2 on both lanes: popcount(1^2)=2 each -> 4
    assert h == 4 and ht == 2
    # v: row0 psum 1->2 (2 toggles), row1 psum 3->6 (popcount(3^6)=2) -> 4
    assert v == 4 and vt == 2
    # OS: A rows stream over K (1->1, 2->2: 0), W column 1->2 (2 toggles)
    h, v, ht, vt = reference.toggle_counts(a, w, 2, 1, 8, 8, "OS")
    assert (h, v, ht, vt) == (0, 2, 2, 1)


def test_reference_matches_the_numpy_oracle():
    from repro.kernels.activity_profile.ref import profile_gemm_toggles_ref

    rng = np.random.default_rng(3)
    a = rng.integers(0, 2**15, size=(20, 40))
    w = rng.integers(-(2**15), 2**15, size=(40, 24))
    for df, rows, cols, b_v in (("WS", 16, 8, 36), ("WS", 12, 16, 36), ("OS", 16, 8, 16)):
        assert reference.toggle_counts(a, w, rows, cols, 16, b_v, df) == (
            profile_gemm_toggles_ref(a, w, rows, cols, 16, b_v, dataflow=df)
        )
