"""The bus-invert coding lowering (``layout.coeffs.grid_coding_effective``).

It evaluates the closed form once per distinct (activity, width) pair of the
coded points and scatters the result back. The oracle is the closed form on
every element: ``np.where(bi, bus_invert_activity_arr(a_v, b_v_data), a_v)``,
which the lowering must match bit for bit on any input.
"""

import contextlib
import json
from pathlib import Path

import numpy as np
import pytest

import repro.layout.coeffs as coeffs
from repro.core.design_space import DesignSpace
from repro.core.optimize import bus_invert_activity_arr
from repro.core.workloads import _activity_classes
from repro.layout.coeffs import clear_coeff_cache, grid_coding_effective
from repro.serving import DEFAULT_SPACE

GRID_EXPLORE = Path(__file__).resolve().parents[1] / "bench/mixes/grid_explore.json"
SMALL = dict(rows=(8, 16, 32), cols=(8, 64), input_bits=(4, 8, 16), dataflows=("WS", "OS"))


def _fleet_grid():
    return DesignSpace(**json.loads(GRID_EXPLORE.read_text())["space"]).expand()


def _class_table_a_v(grid, n_gemms, n_uniq, seed):
    """(G, P) activities gathered from a (unique operand, activity class)
    table, as ``measured_design_gemm_activities`` builds them."""
    rng = np.random.default_rng(seed)
    classes, point_class = _activity_classes(grid)
    table = rng.uniform(0.0, 1.0, (n_uniq, len(classes)))
    return table[rng.integers(0, n_uniq, n_gemms)][:, point_class]


def _fleet_class_table():
    grid = _fleet_grid()
    return grid, _class_table_a_v(grid, 72, 15, seed=1)


def _default_space():
    grid = DEFAULT_SPACE.expand()
    return grid, _class_table_a_v(grid, 9, 4, seed=2)


def _random_no_repeats():
    grid = DesignSpace(**SMALL, bus_invert=(False, True)).expand()
    a_v = np.random.default_rng(3).uniform(0.0, 1.0, (5, grid.n_points))
    assert len(np.unique(a_v)) == a_v.size
    return grid, a_v


def _endpoints():
    grid = DesignSpace(**SMALL, bus_invert=(False, True)).expand()
    fi = np.finfo(np.float64)
    vals = np.asarray([0.0, 1.0, fi.tiny, 1.0 - fi.eps, 0.5])
    return grid, np.resize(vals, (3, grid.n_points))


def _all_off():
    grid = DesignSpace(**SMALL, bus_invert=(False,)).expand()
    return grid, _class_table_a_v(grid, 6, 3, seed=4)


def _all_on():
    grid = DesignSpace(**SMALL, bus_invert=(True,)).expand()
    return grid, _class_table_a_v(grid, 6, 3, seed=5)


def _single_profile():
    grid = DesignSpace(**SMALL, bus_invert=(False, True)).expand()
    return grid, _class_table_a_v(grid, 1, 1, seed=6)


CASES = {
    "fleet_class_table": _fleet_class_table,
    "default_space": _default_space,
    "random_no_repeats": _random_no_repeats,
    "endpoints": _endpoints,
    "bus_invert_all_off": _all_off,
    "bus_invert_all_on": _all_on,
    "single_profile": _single_profile,
}


@pytest.mark.parametrize("case", list(CASES))
def test_grid_coding_effective_bit_equal_to_closed_form(case):
    grid, a_v = CASES[case]()
    bi = np.asarray(grid.bus_invert, bool)
    bits = np.asarray(grid.b_v_data, np.float64)
    want = np.where(bi, bus_invert_activity_arr(a_v, bits), a_v)
    clear_coeff_cache()
    got = grid_coding_effective(grid, a_v)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.float64 and got.shape == want.shape
    # the memo returns the same bits
    np.testing.assert_array_equal(grid_coding_effective(grid, a_v), want)


class _Recorder:
    """Stands in for ``span``: keeps each span's name and metadata."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **stats):
        meta = dict(stats)
        self.spans.append((name, meta))

        @contextlib.contextmanager
        def cm():
            yield type("Ev", (), {"set_metadata": lambda _, **kw: meta.update(kw)})()

        return cm()


def test_coding_counters_recorded(monkeypatch):
    """The span records how many coded entries there are and how many
    distinct pairs reached the closed form; a memo hit evaluates none."""
    rec = _Recorder()
    monkeypatch.setattr(coeffs, "span", rec)
    grid, a_v = _fleet_class_table()
    clear_coeff_cache()
    grid_coding_effective(grid, a_v)
    grid_coding_effective(grid, a_v)
    n_coded = a_v.shape[0] * int(np.asarray(grid.bus_invert, bool).sum())
    (name, miss), (_, hit) = rec.spans
    assert name == "lower.grid_coding_effective"
    assert miss["coded_elements"] == n_coded
    assert 0 < miss["coded_evaluated"] <= miss["coded_elements"]
    # 15 operand classes x the coded points' activity classes, at most
    assert miss["coded_evaluated"] <= 15 * len(_activity_classes(grid)[0])
    assert hit == {"coded_elements": n_coded, "coded_evaluated": 0}


def test_coding_counters_without_repeats(monkeypatch):
    """When no value repeats, every coded element is evaluated once."""
    rec = _Recorder()
    monkeypatch.setattr(coeffs, "span", rec)
    grid, a_v = _random_no_repeats()
    clear_coeff_cache()
    grid_coding_effective(grid, a_v)
    ((_, meta),) = rec.spans
    assert meta["coded_evaluated"] == meta["coded_elements"] > 0
