"""Public API for the fused switching-activity engine.

``profile_gemm_toggles`` returns EXACT integer toggle totals for the
horizontal and vertical buses of a full GEMM under either systolic dataflow:

  * ``dataflow="WS"`` — weight-stationary: horizontal buses stream the A
    operand over the M axis, vertical buses carry the partial-sum cumsum
    down the reduction rows.  Every weight tile, every stream step, without
    ever materializing the (T, R, C) partial-sum tensor.
  * ``dataflow="OS"`` — output-stationary: BOTH buses are operand streams
    over the K axis (A rows horizontally, W columns vertically; the
    accumulators never move).  Per-lane toggle totals are geometry-free and
    scale with the output-tile counts — ceil(N/cols) horizontally,
    ceil(M/rows) vertically — exactly as their transition denominators do,
    so no partial-sum machinery runs at all.

Two engines run the identical algorithm (shared jnp helpers in kernel.py):

  * ``"pallas"`` — the fused TPU kernel (one grid cell per (tile, t-block),
    carry in VMEM scratch). Also runs under ``interpret=True`` for CPU CI.
  * ``"xla"``    — a jitted lax.map-over-tiles / lax.scan-over-time rendering
    of the same grid, for hosts without a TPU. Peak live memory is one
    (block_t, R, C) block, exactly like the kernel.

``engine="auto"`` picks "pallas" on TPU backends and "xla" elsewhere.

Operand contract: values must be int16-range (|x| < 2^15) so products fit
int32 — the paper's quantization (Section IV) and everything
``repro.core.quant`` emits satisfies this. ``repro.core.switching`` falls
back to the numpy oracle for anything wider.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.activity_profile.kernel import (
    activity_profile_pallas,
    choose_block_t,
    operand_stream_toggles_pallas,
    partial_sum_planes,
    planes_toggles,
    value32_toggles,
)

__all__ = [
    "ToggleCounts",
    "LaneToggleCounts",
    "INT16_SAFE_MAX",
    "MAX_FUSED_K",
    "MAX_FUSED_LANES",
    "operands_fit_fused",
    "profile_gemm_toggles",
    "profile_gemm_lane_toggles",
    "stream_toggle_total",
    "stream_lane_toggle_totals",
]

INT16_SAFE_MAX = (1 << 15) - 1
# K_pad (= K + up to rows-1 of zero padding) must stay below this so the
# per-row int32 h-toggle partials (<= K_pad * 64) cannot overflow.
# backend="auto" in repro.core.switching falls back to numpy beyond it.
MAX_FUSED_K = 1 << 25
# The lo/hi int32 cumsum planes are exact only while R * 0xffff fits int32.
MAX_FUSED_ROWS = 1 << 15
# OS streams reduce per-time-row toggle partials over their lane axis (M for
# the A stream, N for the W stream) in int32: lanes * 64 must stay < 2^31.
MAX_FUSED_LANES = 1 << 25


@dataclasses.dataclass(frozen=True)
class ToggleCounts:
    """Exact integer toggle totals + transition denominators for one GEMM."""

    h_toggles: int
    v_toggles: int
    h_transitions: int
    v_transitions: int

    def activities(self, b_h: int, b_v: int) -> tuple[float, float]:
        a_h = self.h_toggles / (self.h_transitions * b_h) if self.h_transitions else 0.0
        a_v = self.v_toggles / (self.v_transitions * b_v) if self.v_transitions else 0.0
        return a_h, a_v

    def __add__(self, other: "ToggleCounts") -> "ToggleCounts":
        return ToggleCounts(
            self.h_toggles + other.h_toggles,
            self.v_toggles + other.v_toggles,
            self.h_transitions + other.h_transitions,
            self.v_transitions + other.v_transitions,
        )


@dataclasses.dataclass(frozen=True)
class LaneToggleCounts:
    """Exact per-bit-lane toggle totals for one GEMM.

    ``h_lanes[b]`` / ``v_lanes[b]`` count the toggles of bus bit-lane ``b``
    (LSB first) summed over every wire bundle and transition of the
    respective direction; every lane shares the bundle's transition
    denominator, so lane activities are ``lanes / transitions`` and the
    lane sums reproduce the aggregate ``ToggleCounts`` bit-exactly
    (``sum(h_lanes) == h_toggles`` etc. — regression-tested).
    """

    h_lanes: tuple[int, ...]
    v_lanes: tuple[int, ...]
    h_transitions: int
    v_transitions: int

    def totals(self) -> ToggleCounts:
        return ToggleCounts(
            sum(self.h_lanes), sum(self.v_lanes), self.h_transitions, self.v_transitions
        )

    def activities(self, b_h: int, b_v: int) -> tuple[float, float]:
        return self.totals().activities(b_h, b_v)


def _fits_int16(arr: np.ndarray) -> bool:
    # Bounds are checked via min/max, NOT np.abs: abs(int64 min) wraps
    # negative and would silently admit an out-of-contract value.
    return not arr.size or (
        -INT16_SAFE_MAX <= int(arr.min()) and int(arr.max()) <= INT16_SAFE_MAX
    )


def operands_fit_fused(a: np.ndarray, w: np.ndarray) -> bool:
    """True iff products fit int32 (int16-range operands) — the engine's contract."""
    return _fits_int16(a) and _fits_int16(w)


@functools.partial(jax.jit, static_argnames=("b_h", "block_t"))
def _h_toggles_xla(a_pad: jnp.ndarray, *, b_h: int, block_t: int) -> jnp.ndarray:
    """Horizontal-bus toggle partials over the whole (T_pad, K_pad) stream.

    One k-strip's horizontal count is identical for every n-tile it pairs
    with, and the strips concatenate to the full matrix — so the total over
    all tiles is ``n_tiles *`` one vectorized pass over ``a``. K zero-padding
    toggles nothing (0 XOR 0). Returns (num_t_blocks, block_t) int32
    partials — reduced per ROW, not per block, so each partial is bounded by
    K_pad * 64 regardless of block_t (< 2^31 for any K_pad < 2^25, enforced
    by the caller).
    """
    t_pad, k_pad = a_pad.shape
    blocks = a_pad.reshape(t_pad // block_t, block_t, k_pad)

    def step(prev_row, blk):
        lag = jnp.concatenate([prev_row, blk[:-1]], axis=0)
        cnt = jnp.sum(value32_toggles(blk, lag, b_h), axis=1)
        return blk[-1:], cnt

    # Seed with t=0 so the first transition contributes zero toggles.
    _, cnts = jax.lax.scan(step, blocks[0, :1], blocks)
    return cnts


@functools.partial(
    jax.jit,
    static_argnames=("rows", "cols", "k", "n", "b_v", "block_t", "tile_chunk"),
)
def _v_toggles_xla(
    a_pad: jnp.ndarray,
    w_pad: jnp.ndarray,
    *,
    rows: int,
    cols: int,
    k: int,
    n: int,
    b_v: int,
    block_t: int,
    tile_chunk: int,
) -> jnp.ndarray:
    """Vertical-bus toggle partials: XLA rendering of the fused kernel grid.

    Sequential over tiles (lax.map) and time blocks (outer lax.scan), with an
    inner lax.scan down R that carries the running partial-sum lo/hi planes —
    S[t, r] is produced as a (block_t, C) slice, toggled against its time
    predecessor, and immediately overwritten. Live memory is O(block_t * C +
    R * C) per tile regardless of T, K, N; the (T, R, C) tensor never exists.

    The running sum adds each raw int32 product with one unsigned-compare
    carry into the hi plane — exact mod 2^64, same invariant as the Pallas
    kernel's plane reconstruction.

    Tiles run ``tile_chunk`` at a time under vmap (one lax.map step per
    chunk): wider vectors amortize scan-step overhead and let XLA:CPU's
    intra-op threads engage, ~4x over strictly-sequential tiles at bounded
    memory (tile_chunk * block_t * cols elements live). Tile ids are padded
    to a chunk multiple by repeating id 0; the caller drops the duplicates.
    Returns (padded_tiles // tile_chunk, tile_chunk, num_t_blocks) int32.
    """
    t_pad, k_pad = a_pad.shape
    k_tiles = k_pad // rows
    n_tiles = w_pad.shape[1] // cols
    num_tb = t_pad // block_t
    a_blocks = a_pad.reshape(num_tb, block_t, k_tiles, rows)
    w_tiles = w_pad.reshape(k_tiles, rows, n_tiles, cols).transpose(0, 2, 1, 3)
    cix = jnp.arange(cols, dtype=jnp.int32)
    rix = jnp.arange(rows, dtype=jnp.int32)

    def per_tile(p):
        kt = p // n_tiles
        nt = p % n_tiles
        w_t = w_tiles[kt, nt]  # (rows, cols)
        a_t = a_blocks[:, :, kt, :]  # (num_tb, block_t, rows)
        valid_r = jnp.minimum(rows, k - kt * rows)
        valid_c = jnp.minimum(cols, n - nt * cols)
        colmask = cix < valid_c  # (cols,)

        def block_step(bcarry, a_blk):
            bound_lo, bound_hi = bcarry  # (rows, cols): S[t_prev_last, r, :]

            def rstep(rcarry, xs):
                run_lo, run_hi = rcarry  # (block_t, cols): S[t, r-1, :]
                a_col, w_row, b_lo, b_hi, r = xs
                prod = a_col[:, None] * w_row[None, :]
                new_lo = run_lo + prod
                carry = (
                    new_lo.astype(jnp.uint32) < run_lo.astype(jnp.uint32)
                ).astype(jnp.int32)
                new_hi = run_hi + (prod >> jnp.int32(31)) + carry
                lag_lo = jnp.concatenate([b_lo[None], new_lo[:-1]], axis=0)
                lag_hi = jnp.concatenate([b_hi[None], new_hi[:-1]], axis=0)
                cnt = planes_toggles(new_lo, new_hi, lag_lo, lag_hi, b_v)
                cnt = jnp.sum(jnp.where((r < valid_r) & colmask[None, :], cnt, 0))
                return (new_lo, new_hi), (cnt, new_lo[-1], new_hi[-1])

            zero = jnp.zeros((a_blk.shape[0], cols), jnp.int32)
            (_, _), (cnts, nb_lo, nb_hi) = jax.lax.scan(
                rstep, (zero, zero), (a_blk.T, w_t, bound_lo, bound_hi, rix)
            )
            return (nb_lo, nb_hi), jnp.sum(cnts)

        # Seed the time-boundary planes with t=0 (zero first-transition).
        s0_lo, s0_hi = partial_sum_planes(a_t[0, :1, :], w_t)
        (_, _), v_b = jax.lax.scan(block_step, (s0_lo[0], s0_hi[0]), a_t)
        return v_b

    num_tiles = k_tiles * n_tiles
    padded = -(-num_tiles // tile_chunk) * tile_chunk
    ids = jnp.where(
        jnp.arange(padded, dtype=jnp.int32) < num_tiles,
        jnp.arange(padded, dtype=jnp.int32),
        0,
    ).reshape(padded // tile_chunk, tile_chunk)
    return jax.lax.map(jax.vmap(per_tile), ids)


def _pad_operands(
    a: np.ndarray, w: np.ndarray, rows: int, cols: int, block_t: int
) -> tuple[np.ndarray, np.ndarray]:
    m, k = a.shape
    n = w.shape[1]
    pt = (-m) % block_t
    pk = (-k) % rows
    pn = (-n) % cols
    # T: replicate the last row — repeated values toggle zero bits, so the
    # padding is count-neutral. K/N: zero-pad; edge-tile masks drop them.
    a_pad = np.pad(a, ((0, pt), (0, pk)), mode="edge" if m else "constant")
    if pk:
        a_pad[:, k:] = 0
    w_pad = np.pad(w, ((0, pk), (0, pn)))
    return a_pad, w_pad


def stream_toggle_total(
    x: np.ndarray,
    bits: int,
    *,
    engine: str = "auto",
    block_t: int | None = None,
    interpret: bool = False,
) -> int:
    """Exact toggle total of a bundle of independent value streams.

    ``x`` is (T, L): L lanes, each a T-step stream of int16-range values
    toggling on a ``bits``-wide two's-complement bus.  This is the whole
    per-operand computation of the OS dataflow (and the h pass of WS, up to
    tiling).  Runs the operand-stream Pallas kernel on TPU hosts and the
    shared scan-free XLA h pass elsewhere; both reuse the WS horizontal
    machinery so the engines stay one algorithm.
    """
    x = np.asarray(x)
    t, lanes = x.shape
    if t < 2 or lanes == 0:
        return 0
    if not _fits_int16(x):
        # validate-or-raise, like profile_gemm_toggles: a silent int32 cast
        # would wrap out-of-contract values into wrong totals
        raise ValueError(
            "fused engine needs int16-range stream values; "
            "use the numpy backend for wider values"
        )
    if lanes >= MAX_FUSED_LANES:
        raise ValueError("fused engine supports < 2^25 stream lanes")
    if block_t is None:
        block_t = min(choose_block_t(1, lanes), -(-t // 8) * 8)
    pt = (-t) % block_t
    # Edge-replicate the stream tail: repeated values toggle zero bits.
    x_pad = np.pad(x.astype(np.int32), ((0, pt), (0, 0)), mode="edge")
    if engine == "auto":
        engine = "pallas" if jax.default_backend() == "tpu" else "xla"
    if engine == "pallas":
        parts = operand_stream_toggles_pallas(
            jnp.asarray(x_pad), bits=bits, block_t=block_t, interpret=interpret
        )
    elif engine == "xla":
        parts = _h_toggles_xla(jnp.asarray(x_pad), b_h=bits, block_t=block_t)
    else:
        raise ValueError(f"unknown engine {engine!r}")
    return int(np.asarray(parts).astype(np.int64).sum())


def _profile_os_toggles(
    a: np.ndarray,
    w: np.ndarray,
    rows: int,
    cols: int,
    b_h: int,
    b_v: int,
    engine: str,
    block_t: int | None,
    interpret: bool,
) -> ToggleCounts:
    """OS totals: per-lane operand-stream toggles scaled by the tile grid.

    Every (mt, nt) output tile streams the SAME A rows (for its mt) and the
    same W columns (for its nt) over the K axis; the fold into full-GEMM
    totals is the shared ``switching.os_stream_counts`` identity.  Edge
    tiles need no masking: summing over the true lanes of ``a``/``w``
    already covers exactly the valid PEs.
    """
    from repro.core.switching import os_stream_counts

    m, k = a.shape
    n = w.shape[1]
    if k < 2 or m == 0 or n == 0:
        return ToggleCounts(*os_stream_counts(0, 0, m, k, n, rows, cols))
    kw = dict(engine=engine, block_t=block_t, interpret=interpret)
    base_h = stream_toggle_total(np.ascontiguousarray(a.T), b_h, **kw)
    base_v = stream_toggle_total(w, b_v, **kw)
    return ToggleCounts(*os_stream_counts(base_h, base_v, m, k, n, rows, cols))


def profile_gemm_toggles(
    a: np.ndarray,
    w: np.ndarray,
    rows: int,
    cols: int,
    b_h: int,
    b_v: int,
    *,
    dataflow: str = "WS",
    engine: str = "auto",
    block_t: int | None = None,
    interpret: bool = False,
) -> ToggleCounts:
    """Exact toggle totals for GEMM ``a @ w`` tiled on an R x C array.

    ``a`` is (M, K), ``w`` is (K, N), integer-valued with int16-range
    magnitudes. Counts match ``repro.core.switching``'s numpy oracle
    bit-for-bit under both dataflows: for WS every ceil(K/rows)*ceil(N/cols)
    weight tile and all M stream steps; for OS every ceil(M/rows)*ceil(N/cols)
    output tile and all K reduction steps. Bus widths ``b_h``/``b_v`` in
    [1, 64].
    """
    a = np.asarray(a)
    w = np.asarray(w)
    if a.ndim != 2 or w.ndim != 2 or a.shape[1] != w.shape[0]:
        raise ValueError(f"bad GEMM shapes {a.shape} x {w.shape}")
    if not 1 <= b_h <= 64 or not 1 <= b_v <= 64:
        raise ValueError("bus widths must be in [1, 64]")
    if dataflow not in ("WS", "OS"):
        raise ValueError(f"unknown dataflow {dataflow!r}")
    if not operands_fit_fused(a, w):
        raise ValueError(
            "fused engine needs int16-range operands (products must fit int32); "
            "use the numpy backend for wider values"
        )
    if dataflow == "OS":
        if max(a.shape[0], w.shape[1]) >= MAX_FUSED_LANES:
            # per-time-row stream partials are bounded by lanes * 64
            raise ValueError(
                "fused OS engine supports M, N < 2^25; use the numpy backend"
            )
        if engine == "auto":
            engine = "pallas" if jax.default_backend() == "tpu" else "xla"
        return _profile_os_toggles(
            a, w, rows, cols, b_h, b_v, engine, block_t, interpret
        )
    if a.shape[1] + rows >= MAX_FUSED_K:
        # per-row int32 h-toggle partials are bounded by K_pad * 64
        raise ValueError("fused engine supports K < 2^25; use the numpy backend")
    if rows >= MAX_FUSED_ROWS:
        raise ValueError("fused engine supports rows < 2^15; use the numpy backend")
    m, k = a.shape
    n = w.shape[1]
    k_tiles = -(-k // rows) if k else 0
    n_tiles = -(-n // cols) if n else 0
    h_trans = max(m - 1, 0) * k * n_tiles
    v_trans = max(m - 1, 0) * k * n
    if m < 2 or k == 0 or n == 0:
        return ToggleCounts(0, 0, h_trans, v_trans)

    if block_t is None:
        # Don't pad T beyond the next 8-multiple of the true stream length.
        block_t = min(choose_block_t(rows, cols), -(-m // 8) * 8)
    a_pad, w_pad = _pad_operands(a.astype(np.int32), w.astype(np.int32), rows, cols, block_t)

    if engine == "auto":
        engine = "pallas" if jax.default_backend() == "tpu" else "xla"
    if engine == "pallas":
        h_parts, v_parts = activity_profile_pallas(
            jnp.asarray(a_pad),
            jnp.asarray(w_pad),
            rows=rows,
            cols=cols,
            k=k,
            b_h=b_h,
            b_v=b_v,
            block_t=block_t,
            interpret=interpret,
        )
        h_tog = int(np.asarray(h_parts).astype(np.int64).sum())
    elif engine == "xla":
        num_tiles = k_tiles * n_tiles
        tile_chunk = int(min(16, max(1, num_tiles)))
        h_strip = _h_toggles_xla(jnp.asarray(a_pad), b_h=b_h, block_t=block_t)
        v_parts = _v_toggles_xla(
            jnp.asarray(a_pad),
            jnp.asarray(w_pad),
            rows=rows,
            cols=cols,
            k=k,
            n=n,
            b_v=b_v,
            block_t=block_t,
            tile_chunk=tile_chunk,
        )
        # Drop the chunk-padding duplicates before reducing.
        v_parts = np.asarray(v_parts).reshape(-1, v_parts.shape[-1])[:num_tiles]
        h_tog = n_tiles * int(np.asarray(h_strip).astype(np.int64).sum())
    else:
        raise ValueError(f"unknown engine {engine!r}")

    v_tog = int(np.asarray(v_parts).astype(np.int64).sum())
    return ToggleCounts(h_tog, v_tog, h_trans, v_trans)


# ---------------------------------------------------------------------------
# Per-bit-lane toggle totals (lane-resolved rendering of the same passes)
# ---------------------------------------------------------------------------
#
# The aggregate engines popcount the XORed lo/hi planes; the lane-resolved
# variants extract each bus bit instead and accumulate a (lanes,) vector.
# Bus semantics match ``kernel.value32_toggles`` / ``kernel.planes_toggles``
# exactly: for a bus wider than the 32-bit operand plane, lanes >= 32 of an
# operand stream are sign-extension copies (they all flip with the sign
# bit), while the WS partial-sum lanes >= 32 come from the true hi plane.
# The lane passes always run the XLA engine (lane extraction is a reduction
# fan-out, not a kernel-shaped inner loop); counts are bit-exact vs the
# aggregate engines and the numpy oracle.


def _compact_lanes(bits: int) -> int:
    """Lanes materialized on-device: 32 value lanes + one shared sign lane."""
    return min(bits, 32) + (1 if bits > 32 else 0)


def _expand_sign_lanes(cnt: np.ndarray, bits: int) -> np.ndarray:
    """(compact,) device counts -> (bits,) int64 per-lane totals."""
    cnt = np.asarray(cnt, np.int64)
    if bits <= 32:
        return cnt
    return np.concatenate([cnt[:32], np.repeat(cnt[32:33], bits - 32)])


@functools.partial(jax.jit, static_argnames=("bits", "block_t"))
def _h_lane_toggles_xla(a_pad: jnp.ndarray, *, bits: int, block_t: int) -> jnp.ndarray:
    """Per-bit-lane horizontal toggle partials over the whole stream.

    Returns (num_t_blocks, block_t, compact_lanes) int32 — reduced per time
    ROW, so each partial is bounded by K_pad (< 2^25, caller-enforced).
    """
    t_pad, k_pad = a_pad.shape
    blocks = a_pad.reshape(t_pad // block_t, block_t, k_pad)

    def lane_counts(x):  # (block_t, k_pad) int32 XOR -> (block_t, compact)
        cols_ = [((x >> jnp.int32(b)) & 1).sum(axis=1) for b in range(min(bits, 32))]
        if bits > 32:
            cols_.append(((x >> jnp.int32(31)) & 1).sum(axis=1))
        return jnp.stack(cols_, axis=-1).astype(jnp.int32)

    def step(prev_row, blk):
        lag = jnp.concatenate([prev_row, blk[:-1]], axis=0)
        return blk[-1:], lane_counts(blk ^ lag)

    _, cnts = jax.lax.scan(step, blocks[0, :1], blocks)
    return cnts


@functools.partial(
    jax.jit,
    static_argnames=("rows", "cols", "k", "n", "b_v", "block_t", "tile_chunk"),
)
def _v_lane_toggles_xla(
    a_pad: jnp.ndarray,
    w_pad: jnp.ndarray,
    *,
    rows: int,
    cols: int,
    k: int,
    n: int,
    b_v: int,
    block_t: int,
    tile_chunk: int,
) -> jnp.ndarray:
    """Per-bit-lane vertical toggle partials: lane-resolved ``_v_toggles_xla``.

    Same grid, same lo/hi-plane carries; each (tile, t-block) cell reduces a
    (b_v,) lane vector instead of a popcount scalar (every entry is bounded
    by block_t * rows * cols < 2^31).  Returns
    (padded_tiles // tile_chunk, tile_chunk, num_t_blocks, b_v) int32.
    """
    t_pad, k_pad = a_pad.shape
    k_tiles = k_pad // rows
    n_tiles = w_pad.shape[1] // cols
    num_tb = t_pad // block_t
    a_blocks = a_pad.reshape(num_tb, block_t, k_tiles, rows)
    w_tiles = w_pad.reshape(k_tiles, rows, n_tiles, cols).transpose(0, 2, 1, 3)
    cix = jnp.arange(cols, dtype=jnp.int32)
    rix = jnp.arange(rows, dtype=jnp.int32)

    def per_tile(p):
        kt = p // n_tiles
        nt = p % n_tiles
        w_t = w_tiles[kt, nt]
        a_t = a_blocks[:, :, kt, :]
        valid_r = jnp.minimum(rows, k - kt * rows)
        valid_c = jnp.minimum(cols, n - nt * cols)
        colmask = cix < valid_c

        def block_step(bcarry, a_blk):
            bound_lo, bound_hi = bcarry

            def rstep(rcarry, xs):
                run_lo, run_hi = rcarry
                a_col, w_row, b_lo, b_hi, r = xs
                prod = a_col[:, None] * w_row[None, :]
                new_lo = run_lo + prod
                carry = (
                    new_lo.astype(jnp.uint32) < run_lo.astype(jnp.uint32)
                ).astype(jnp.int32)
                new_hi = run_hi + (prod >> jnp.int32(31)) + carry
                lag_lo = jnp.concatenate([b_lo[None], new_lo[:-1]], axis=0)
                lag_hi = jnp.concatenate([b_hi[None], new_hi[:-1]], axis=0)
                x_lo = new_lo ^ lag_lo
                x_hi = new_hi ^ lag_hi
                ok = (r < valid_r) & colmask[None, :]
                lanes = [
                    jnp.sum(jnp.where(ok, (x_lo >> jnp.int32(b)) & 1, 0))
                    for b in range(min(b_v, 32))
                ] + [
                    jnp.sum(jnp.where(ok, (x_hi >> jnp.int32(b - 32)) & 1, 0))
                    for b in range(32, b_v)
                ]
                cnt = jnp.stack(lanes).astype(jnp.int32)
                return (new_lo, new_hi), (cnt, new_lo[-1], new_hi[-1])

            zero = jnp.zeros((a_blk.shape[0], cols), jnp.int32)
            (_, _), (cnts, nb_lo, nb_hi) = jax.lax.scan(
                rstep, (zero, zero), (a_blk.T, w_t, bound_lo, bound_hi, rix)
            )
            return (nb_lo, nb_hi), jnp.sum(cnts, axis=0)

        s0_lo, s0_hi = partial_sum_planes(a_t[0, :1, :], w_t)
        (_, _), v_b = jax.lax.scan(block_step, (s0_lo[0], s0_hi[0]), a_t)
        return v_b  # (num_tb, b_v)

    num_tiles = k_tiles * n_tiles
    padded = -(-num_tiles // tile_chunk) * tile_chunk
    ids = jnp.where(
        jnp.arange(padded, dtype=jnp.int32) < num_tiles,
        jnp.arange(padded, dtype=jnp.int32),
        0,
    ).reshape(padded // tile_chunk, tile_chunk)
    return jax.lax.map(jax.vmap(per_tile), ids)


def stream_lane_toggle_totals(
    x: np.ndarray, bits: int, *, block_t: int | None = None
) -> np.ndarray:
    """Per-bit-lane totals of ``stream_toggle_total``: (bits,) int64.

    ``x`` is (T, L) int16-range stream lanes on a ``bits``-wide bus; entry b
    counts the toggles of bus bit b summed over all L wires and T-1
    transitions (``sum(result) == stream_toggle_total(x, bits)``,
    bit-exactly).
    """
    x = np.asarray(x)
    t, lanes = x.shape
    if t < 2 or lanes == 0:
        return np.zeros(bits, np.int64)
    if not _fits_int16(x):
        raise ValueError(
            "fused engine needs int16-range stream values; "
            "use the numpy backend for wider values"
        )
    if lanes >= MAX_FUSED_LANES:
        raise ValueError("fused engine supports < 2^25 stream lanes")
    if block_t is None:
        block_t = min(choose_block_t(1, lanes), -(-t // 8) * 8)
    pt = (-t) % block_t
    x_pad = np.pad(x.astype(np.int32), ((0, pt), (0, 0)), mode="edge")
    parts = _h_lane_toggles_xla(jnp.asarray(x_pad), bits=bits, block_t=block_t)
    compact = np.asarray(parts).astype(np.int64).sum(axis=(0, 1))
    return _expand_sign_lanes(compact, bits)


def profile_gemm_lane_toggles(
    a: np.ndarray,
    w: np.ndarray,
    rows: int,
    cols: int,
    b_h: int,
    b_v: int,
    *,
    dataflow: str = "WS",
    block_t: int | None = None,
) -> LaneToggleCounts:
    """Exact per-bit-lane toggle totals for GEMM ``a @ w`` on an R x C array.

    The lane-resolved sibling of ``profile_gemm_toggles`` (same operand and
    dimension contracts, same tiling semantics under both dataflows); the
    lane sums equal the aggregate totals bit-for-bit.  Always runs the XLA
    engine.
    """
    a = np.asarray(a)
    w = np.asarray(w)
    if a.ndim != 2 or w.ndim != 2 or a.shape[1] != w.shape[0]:
        raise ValueError(f"bad GEMM shapes {a.shape} x {w.shape}")
    if not 1 <= b_h <= 64 or not 1 <= b_v <= 64:
        raise ValueError("bus widths must be in [1, 64]")
    if dataflow not in ("WS", "OS"):
        raise ValueError(f"unknown dataflow {dataflow!r}")
    if not operands_fit_fused(a, w):
        raise ValueError(
            "fused engine needs int16-range operands (products must fit int32); "
            "use the numpy backend for wider values"
        )
    m, k = a.shape
    n = w.shape[1]

    if dataflow == "OS":
        if max(m, n) >= MAX_FUSED_LANES:
            raise ValueError(
                "fused OS engine supports M, N < 2^25; use the numpy backend"
            )
        from repro.core.switching import os_stream_counts

        _, _, h_trans, v_trans = os_stream_counts(0, 0, m, k, n, rows, cols)
        if k < 2 or m == 0 or n == 0:
            return LaneToggleCounts((0,) * b_h, (0,) * b_v, h_trans, v_trans)
        base_h = stream_lane_toggle_totals(
            np.ascontiguousarray(a.T), b_h, block_t=block_t
        )
        base_v = stream_lane_toggle_totals(w, b_v, block_t=block_t)
        n_tiles = -(-n // cols)
        m_tiles = -(-m // rows)
        return LaneToggleCounts(
            tuple(int(v) for v in n_tiles * base_h),
            tuple(int(v) for v in m_tiles * base_v),
            h_trans,
            v_trans,
        )

    if k + rows >= MAX_FUSED_K:
        raise ValueError("fused engine supports K < 2^25; use the numpy backend")
    if rows >= MAX_FUSED_ROWS:
        raise ValueError("fused engine supports rows < 2^15; use the numpy backend")
    k_tiles = -(-k // rows) if k else 0
    n_tiles = -(-n // cols) if n else 0
    h_trans = max(m - 1, 0) * k * n_tiles
    v_trans = max(m - 1, 0) * k * n
    if m < 2 or k == 0 or n == 0:
        return LaneToggleCounts((0,) * b_h, (0,) * b_v, h_trans, v_trans)

    if block_t is None:
        block_t = min(choose_block_t(rows, cols), -(-m // 8) * 8)
    a_pad, w_pad = _pad_operands(
        a.astype(np.int32), w.astype(np.int32), rows, cols, block_t
    )
    h_parts = _h_lane_toggles_xla(jnp.asarray(a_pad), bits=b_h, block_t=block_t)
    h_lanes = n_tiles * _expand_sign_lanes(
        np.asarray(h_parts).astype(np.int64).sum(axis=(0, 1)), b_h
    )
    num_tiles = k_tiles * n_tiles
    tile_chunk = int(min(16, max(1, num_tiles)))
    v_parts = _v_lane_toggles_xla(
        jnp.asarray(a_pad),
        jnp.asarray(w_pad),
        rows=rows,
        cols=cols,
        k=k,
        n=n,
        b_v=b_v,
        block_t=block_t,
        tile_chunk=tile_chunk,
    )
    v_parts = np.asarray(v_parts).reshape(-1, v_parts.shape[-2], b_v)[:num_tiles]
    v_lanes = v_parts.astype(np.int64).sum(axis=(0, 1))
    return LaneToggleCounts(
        tuple(int(v) for v in h_lanes),
        tuple(int(v) for v in v_lanes),
        h_trans,
        v_trans,
    )
