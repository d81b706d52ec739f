"""The plain J/op reference agrees with the program's float64 path on every
family, and the reference's operand synthesis with the program's."""

import numpy as np
import pytest

from bench import plain_jop, reference


def _space():
    from repro.core.design_space import DesignSpace

    return DesignSpace(
        rows=(8, 16, 32), cols=(8, 32), input_bits=(4, 16), dataflows=("WS", "OS"),
        bus_invert=(False, True), pe_area_um2=(400.0, 2500.0),
    )


@pytest.mark.parametrize("layout", ["uniform", "serpentine2", "serpentine4", "pods2x2", "pods4x4"])
def test_plain_pricing_matches_the_float64_path(layout):
    from repro.core.objective import evaluate_fleet_objective
    from repro.core.workloads import Gemm

    space = _space()
    grid = space.expand()
    rng = np.random.default_rng(7)
    gemms = [Gemm("g0", 1, 4096, 14336), Gemm("g1", 64, 4096, 1024),
             Gemm("g2", 300, 700, 50), Gemm("g3", 8, 40, 8)]
    a_h = rng.uniform(0.05, 0.5, size=(len(gemms), grid.n_points))
    a_v = rng.uniform(0.05, 0.5, size=(len(gemms), grid.n_points))
    weights = np.array([0.5, 0.3, 0.15, 0.05])
    ev = evaluate_fleet_objective(grid, a_h, a_v, gemms, layouts=(layout,), weights=weights,
                                  use_jit=False, macs_per_token=1e9)
    dims = [(g.m, g.k, g.n) for g in gemms]
    for p in range(grid.n_points):
        want = np.asarray(ev.j_per_mac)[:, 0, p]
        got = plain_jop.price_cell(layout, plain_jop.point_of(space, p), dims, weights,
                                   a_h[:, p], a_v[:, p])
        assert np.array_equal(np.isfinite(got), np.isfinite(want))
        fin = np.isfinite(want)
        # The program's aspect search ends at a 2**-16 bracket, which leaves
        # up to about 2e-6 where the objective has a kink (a segment reaching
        # the repeater spacing); elsewhere the two agree to about 1e-9.
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)
        tok = plain_jop.fleet_j_per_mac(got, weights) * 1e9
        assert tok == pytest.approx(float(ev.j_per_token_robust[0, p]), rel=1e-5, abs=0)


def test_points_follow_the_grid():
    grid = _space().expand()
    for p in range(grid.n_points):
        pt = plain_jop.point_of(_space(), p)
        assert (pt.rows, pt.cols, pt.bits, pt.os, pt.bus_invert, pt.area, pt.b_v) == (
            grid.rows[p], grid.cols[p], grid.b_h[p], grid.dataflow_os[p],
            grid.bus_invert[p], grid.pe_area_um2[p], grid.b_v[p])


@pytest.mark.parametrize("density", [None, 0.35])
def test_operands_match_the_program_synthesis(density):
    from repro.core.workloads import Gemm, gemm_job, gemm_profile_seed

    g = Gemm("x", 200, 600, 300)
    seed = gemm_profile_seed(g, density=density)
    job = gemm_job(g, rows=16, cols=8, bits=8, seed=seed, density=density)
    a, w = job.make()
    ra, rw = reference.synth_operands(*job.shape, 8, density)
    assert np.array_equal(a, ra) and np.array_equal(w, rw)
