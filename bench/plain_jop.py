"""A plain float64 pricing of single (layout, point) cells of an answer.

Written from the model's equations (DESIGN.md sections 2, Layout-engine,
Evaluation-protocol and Objective) and imports nothing of the program. For
one cell it places every PE of the layout family, takes every wire segment
between neighbouring cells as the Manhattan distance of their origins,
prices each segment at ``0.5 c L rep(L) act Vdd^2 f``, finds the robust PE
aspect by a golden-section search over the envelope, and adds the H-tree
clock, the calibrated static power and the pod-partition traffic:

    j_per_mac = (P_bus(t) + P_clk(t) + P_static) / (f R C util)
              + spill_words_per_mac * E_spill(t) + trunk_words_per_mac * E_trunk(t)

at the robust ``t = sqrt(aspect)``. The answer's question supplies the
inputs: the design space's axes, the GEMMs with their MAC-share weights and
MACs per token, and each GEMM's measured activities at the point.
"""

from __future__ import annotations

import dataclasses
import re
from math import lgamma

import numpy as np

# Power model constants (DESIGN.md section 2; the program's defaults).
VDD = 0.9
FREQ_HZ = 1.0e9
WIRE_CAP_F_PER_UM = 0.20e-15
REPEATER_SPACING_UM = 200.0
REPEATER_OVERHEAD = 0.3
CLOCK_TOGGLES_PER_CYCLE = 2.0
NON_BUS_INTERCONNECT_FRACTION = 0.513
INTERCONNECT_SHARE_OF_TOTAL = 0.231
GUTTER_UM = 25.0
PREF = 0.5 * WIRE_CAP_F_PER_UM * VDD * VDD * FREQ_HZ  # W per (um x switching wire)


def ceil_log2(x: int) -> int:
    return max(int(x) - 1, 0).bit_length()


@dataclasses.dataclass(frozen=True)
class Point:
    rows: int
    cols: int
    bits: int
    os: bool
    bus_invert: bool
    area: float
    aspect_lo: float
    aspect_hi: float

    @property
    def b_v_data(self) -> int:
        # OS streams W operands down the columns; WS moves R-deep partial sums
        return self.bits if self.os else 2 * self.bits + ceil_log2(self.rows)

    @property
    def b_v(self) -> int:
        return self.b_v_data + int(self.bus_invert)


def point_of(space, p: int) -> Point:
    """Design point ``p`` of ``space``: the axes' cross product in C order,
    rows slowest and PE area fastest."""
    axes = (space.rows, space.cols, space.input_bits, space.dataflows,
            space.bus_invert, space.pe_area_um2)
    i = np.unravel_index(int(p), tuple(len(a) for a in axes))
    return Point(
        rows=int(space.rows[i[0]]),
        cols=int(space.cols[i[1]]),
        bits=int(space.input_bits[i[2]]),
        os=space.dataflows[i[3]] == "OS",
        bus_invert=bool(space.bus_invert[i[4]]),
        area=float(space.pe_area_um2[i[5]]),
        aspect_lo=float(space.aspect_lo),
        aspect_hi=float(space.aspect_hi),
    )


def family(name: str) -> tuple[str, int]:
    """(kind, parameter): uniform, serpentine with its folds, pods with k."""
    if name == "uniform":
        return "uniform", 1
    m = re.fullmatch(r"serpentine(\d+)", name)
    if m:
        return "serpentine", int(m.group(1))
    m = re.fullmatch(r"pods(\d+)x\1", name)
    if m:
        return "pods", int(m.group(1))
    raise KeyError(f"unknown layout {name!r}")


def feasible(kind: str, f: int, pt: Point) -> bool:
    if kind == "serpentine":
        return pt.cols % f == 0 and pt.cols >= f
    if kind == "pods":
        return pt.rows % f == 0 and pt.cols % f == 0 and pt.rows >= f and pt.cols >= f
    return True


def coded_activity(a: float, bits: int) -> float:
    """Per-wire activity of a ``bits``-bit bus under bus-invert coding: with
    d ~ Binomial(bits, a) data bits flipping, the coded bus of bits + 1
    wires toggles min(d, bits + 1 - d) of them."""
    if a <= 0.0:
        return 0.0
    if a >= 1.0:
        return 1.0 / (bits + 1)
    d = np.arange(bits + 1, dtype=np.float64)
    log_c = np.array([_log_comb(bits, k) for k in range(bits + 1)])
    pmf = np.exp(log_c + d * np.log(a) + (bits - d) * np.log1p(-a))
    return float(np.sum(pmf * np.minimum(d, bits + 1 - d)) / (bits + 1))


def _log_comb(n: int, k: int) -> float:
    return lgamma(n + 1) - lgamma(k + 1) - lgamma(n - k + 1)


def _placement(kind: str, f: int, pt: Point):
    """Cell origins of every PE as (x_w, x_c, y_h, y_c): x = x_w W + x_c and
    y = y_h H + y_c, each (rows, cols). Row 0 is the top edge."""
    r = np.arange(pt.rows)[:, None] + np.zeros((1, pt.cols), np.int64)
    c = np.arange(pt.cols)[None, :] + np.zeros((pt.rows, 1), np.int64)
    zero = np.zeros_like(r, dtype=np.float64)
    if kind == "serpentine":
        band_cols = pt.cols // f
        band = c // band_cols
        pos = np.where(band % 2 == 0, c % band_cols, band_cols - 1 - c % band_cols)
        return pos.astype(float), zero, (band * pt.rows + r).astype(float), zero
    if kind == "pods":
        g = GUTTER_UM if f > 1 else 0.0
        xc = (c // (pt.cols // f)) * g
        yc = (r // (pt.rows // f)) * g
        return c.astype(float), xc.astype(float), r.astype(float), yc.astype(float)
    return c.astype(float), zero, r.astype(float), zero


@dataclasses.dataclass(frozen=True)
class Wires:
    """The data-net segments of one cell, grouped: each row one kind of
    segment with its count, ``|dx_w W + dx_c| + |dy_h H + dy_c|`` length
    terms, width in wires and net (0 horizontal operand bus, 1 vertical)."""

    count: np.ndarray
    dx_w: np.ndarray
    dx_c: np.ndarray
    dy_h: np.ndarray
    dy_c: np.ndarray
    width: np.ndarray
    net: np.ndarray

    def lengths(self, w: float, h: float) -> np.ndarray:
        return np.abs(self.dx_w * w + self.dx_c) + np.abs(self.dy_h * h + self.dy_c)


def data_wires(kind: str, f: int, pt: Point) -> tuple[Wires, int]:
    """Every h and v segment of the placed array; also the width of an
    interior vertical hop (pods under WS carry only the pod-local
    accumulator there)."""
    xw, xc, yh, yc = _placement(kind, f, pt)
    rows_, cols_ = pt.rows, pt.cols
    pods = kind == "pods" and f > 1
    b_v_in = pt.b_v
    if pods and not pt.os:
        b_v_in = min(pt.b_v, 2 * pt.bits + ceil_log2(max(rows_ // f, 1)))
    parts = []
    # h: a feed of length W into each row's first PE, then a hop between
    # consecutive logical columns
    parts.append(np.column_stack([np.ones(rows_), np.zeros(rows_), np.zeros(rows_),
                                  np.zeros(rows_), np.full(rows_, pt.bits), np.zeros(rows_)]))
    if cols_ > 1:
        d = [a[:, 1:] - a[:, :-1] for a in (xw, xc, yh, yc)]
        n = d[0].size
        parts.append(np.column_stack([x.ravel() for x in d]
                                     + [np.full(n, pt.bits), np.zeros(n)]))
    # v: a hop between consecutive logical rows (full width where it crosses
    # a pod boundary), then an output hop of length H off the bottom edge
    if rows_ > 1:
        d = [a[1:, :] - a[:-1, :] for a in (xw, xc, yh, yc)]
        r_idx = np.arange(1, rows_)[:, None] + np.zeros((1, cols_), np.int64)
        cross = (r_idx % (rows_ // f) == 0) if pods else np.zeros_like(r_idx, bool)
        width = np.where(cross, pt.b_v, b_v_in)
        n = d[0].size
        parts.append(np.column_stack([x.ravel() for x in d]
                                     + [width.ravel(), np.ones(n)]))
    parts.append(np.column_stack([np.zeros(cols_), np.zeros(cols_), np.ones(cols_),
                                  np.zeros(cols_), np.full(cols_, pt.b_v), np.ones(cols_)]))
    rows_all = np.concatenate(parts)
    uniq, count = np.unique(rows_all, axis=0, return_counts=True)
    return Wires(count.astype(float), *(uniq[:, i] for i in range(6))), b_v_in


def _htree(we: float, he: float, depth: int) -> float:
    """Total length of a ``depth``-level H-tree over a (we, he) box: level l
    draws 2^(l-1) bars, horizontal of length we / 2^ceil(l/2) at odd l,
    vertical of length he / 2^(l/2) at even l."""
    total = 0.0
    for lvl in range(1, depth + 1):
        bar = we / 2.0 ** ((lvl + 1) // 2) if lvl % 2 else he / 2.0 ** (lvl // 2)
        total += 2.0 ** (lvl - 1) * bar
    return total


def clock_length(kind: str, f: int, pt: Point, w: float, h: float) -> float:
    depth = lambda n: max(1, ceil_log2(n))
    if kind == "pods" and f > 1:
        g = GUTTER_UM
        we, he = pt.cols * w + (f - 1) * g, pt.rows * h + (f - 1) * g
        pr, pc = pt.rows // f, pt.cols // f
        return _htree(we, he, depth(f * f)) + f * f * _htree(pc * w, pr * h, depth(pr * pc))
    if kind == "serpentine":
        we, he = pt.cols / f * w, f * pt.rows * h
    else:
        we, he = pt.cols * w, pt.rows * h
    return _htree(we, he, depth(pt.rows * pt.cols))


def _rep(length):
    return 1.0 + REPEATER_OVERHEAD * np.maximum(length / REPEATER_SPACING_UM - 1.0, 0.0)


def partition(m: int, k: int, n: int, rows: int, cols: int, pods: int, os: bool):
    """(utilization, spill words per MAC, trunk words per MAC) of one GEMM.

    The rows hold the stationary dimension (K under WS, M under OS). Tile
    parallel: the pods take independent tiles, and a K deeper than a pod
    spills each partial output out and back once per extra pass. K-split
    (WS only): K spread down the pod rows, partials crossing the gutters.
    The mapping with fewer rounds wins, ties going to fewer wire hops.
    """
    cdiv = lambda a, b: -(-a // b)
    pr, pc = rows // pods, cols // pods
    stat, stream = (m, k) if os else (k, m)
    macs = m * k * n
    rounds_t = cdiv(cdiv(stat, pr) * cdiv(n, pc), pods * pods)
    spill_t = 0 if os else (cdiv(k, pr) - 1) * m * n
    rounds_s = cdiv(cdiv(stat, rows) * cdiv(n, pc), pods)
    spill_s = (cdiv(k, rows) - 1) * m * n
    trunk_s = cdiv(k, rows) * m * n * (pods - 1)
    cost_t, cost_s = 2 * rows * spill_t, 2 * rows * spill_s + trunk_s
    ksplit = not os and (rounds_s < rounds_t or (rounds_s == rounds_t and cost_s < cost_t))
    rounds, spill, trunk = (rounds_s, spill_s, trunk_s) if ksplit else (rounds_t, spill_t, 0)
    denom = rounds * rows * cols * stream
    if macs <= 0 or denom <= 0:
        return 0.0, 0.0, 0.0
    return macs / denom, spill / macs, trunk / macs


def _golden_min(fn, lo: float, hi: float, iters: int = 200) -> float:
    inv = (np.sqrt(5.0) - 1.0) / 2.0
    a, b = lo, hi
    c, d = b - inv * (b - a), a + inv * (b - a)
    fc, fd = fn(c), fn(d)
    for _ in range(iters):
        if b - a <= 1e-15 * max(1.0, abs(b)):
            break
        if fc <= fd:
            b, d, fd = d, c, fc
            c = b - inv * (b - a)
            fc = fn(c)
        else:
            a, c, fc = c, d, fd
            d = a + inv * (b - a)
            fd = fn(d)
    cands = [(fn(x), x) for x in (lo, hi, 0.5 * (a + b))]
    return min(cands)[1]


def price_cell(layout: str, pt: Point, gemms, weights, a_h, a_v) -> np.ndarray:
    """(G,) joules per useful MAC of every GEMM at one (layout, point) cell,
    ``inf`` where the family does not tile the point or a GEMM has no
    useful MAC there. ``gemms`` are (m, k, n); ``a_h``/``a_v`` each GEMM's
    measured (uncoded) activities at the point."""
    kind, f = family(layout)
    n_g = len(gemms)
    if not feasible(kind, f, pt):
        return np.full(n_g, np.inf)
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    a_h = np.asarray(a_h, np.float64)
    a_v = np.asarray(a_v, np.float64)
    if pt.bus_invert:
        a_v = np.asarray([coded_activity(a, pt.b_v_data) for a in a_v])
    wires, b_v_in = data_wires(kind, f, pt)
    sqa = np.sqrt(pt.area)

    def switched(t):  # (2,) um x wires of the h and v nets at t = sqrt(aspect)
        ln = wires.lengths(sqa * t, sqa / t)
        per = wires.count * ln * _rep(ln) * wires.width
        return np.array([per[wires.net == 0].sum(), per[wires.net == 1].sum()])

    a_bar = np.array([w @ a_h, w @ a_v])
    t = _golden_min(lambda t: float(switched(t) @ a_bar),
                    np.sqrt(pt.aspect_lo), np.sqrt(pt.aspect_hi))
    wd, ht = sqa * t, sqa / t
    sw = switched(t)
    p_bus = PREF * (sw[0] * a_h + sw[1] * a_v)
    p_clk = PREF * CLOCK_TOGGLES_PER_CYCLE * clock_length(kind, f, pt, wd, ht)
    # section 2: the square-layout bus power anchors the fixed non-bus
    # interconnect and the compute term
    bus_sq = PREF * pt.rows * pt.cols * sqa * (pt.bits * a_h + pt.b_v * a_v)
    inter_sq = bus_sq / (1.0 - NON_BUS_INTERCONNECT_FRACTION)
    static = inter_sq * NON_BUS_INTERCONNECT_FRACTION + (
        inter_sq / INTERCONNECT_SHARE_OF_TOTAL - inter_sq
    )
    # a spilled partial goes out and back over 2 R interior vertical hops;
    # a K-split partial crosses one gutter trunk at the full bus width
    e_len = PREF / FREQ_HZ
    e_spill = 2.0 * pt.rows * e_len * ht * _rep(ht) * a_v * b_v_in
    trunk_len = ht + (GUTTER_UM if kind == "pods" and f > 1 else 0.0)
    e_trunk = e_len * trunk_len * _rep(trunk_len) * a_v * pt.b_v
    pods = f if kind == "pods" else 1
    out = np.full(n_g, np.inf)
    for i, (m, k, n) in enumerate(gemms):
        util, spill, trunk = partition(int(m), int(k), int(n), pt.rows, pt.cols, pods, pt.os)
        if util > 0.0:
            p_tot = p_bus[i] + p_clk + static[i]
            out[i] = (p_tot / (FREQ_HZ * pt.rows * pt.cols * util)
                      + spill * e_spill[i] + trunk * e_trunk[i])
    return out


def fleet_j_per_mac(j_per_mac: np.ndarray, weights) -> float:
    """The MAC-weighted fleet J/op of one cell over its live GEMMs."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    live = (w > 0.0) & np.isfinite(j_per_mac)
    if not live.any():
        return float("inf")
    return float(np.sum(w[live] * j_per_mac[live]))
