"""The one query generator: a mix file and a seed -> the window's queries.

A mix names a traffic preset, a design grid and layout families, and the
knobs each query draws:

- ``ratio``: the prefill:decode token ratio (``TrafficModel.with_ratio``);
- ``traffic_seed``: a fresh ``TrafficModel.seed`` per query;
- ``pe_area_scale``: one factor on every PE area of the grid, as in a move
  to another process node.

A ranged knob takes the log-spaced midpoints of ``queries`` equal strata of
its range, and the seed only orders them. So every seed asks the same
amount of work in another order, and runs with different seeds stay
comparable.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Query:
    index: int
    ratio: float | None
    traffic_seed: int | None
    pe_area_scale: float | None


def log_strata(lo: float, hi: float, k: int) -> np.ndarray:
    """Midpoints, in log space, of ``k`` equal strata of [lo, hi]."""
    edges = np.linspace(np.log(lo), np.log(hi), k + 1)
    return np.exp(0.5 * (edges[:-1] + edges[1:]))


def make_queries(mix: dict, seed: int) -> list[Query]:
    k = int(mix["queries"])
    rng = np.random.default_rng(int(seed))

    def ranged(key):
        knob = mix.get(key)
        if not knob:
            return [None] * k
        return [float(v) for v in log_strata(knob["lo"], knob["hi"], k)[rng.permutation(k)]]

    ratios = ranged("ratio")
    scales = ranged("pe_area_scale")
    seeds = (
        [int(s) for s in rng.integers(0, 2**31 - 1, size=k)]
        if mix.get("traffic_seed")
        else [None] * k
    )
    return [Query(i, ratios[i], seeds[i], scales[i]) for i in range(k)]


def build_inputs(mix: dict, query: Query):
    """(TrafficModel, DesignSpace) of one query, as ``codesign`` takes them."""
    from repro.core.design_space import DesignSpace
    from repro.serving.traffic import get_preset

    traffic = get_preset(mix["preset"])
    if query.ratio is not None:
        traffic = traffic.with_ratio(query.ratio)
    if query.traffic_seed is not None:
        traffic = dataclasses.replace(traffic, seed=query.traffic_seed)
    axes = dict(mix["space"])
    if query.pe_area_scale is not None:
        axes["pe_area_um2"] = [a * query.pe_area_scale for a in axes["pe_area_um2"]]
    return traffic, DesignSpace(**axes)
