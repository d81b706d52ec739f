"""Fused Pallas TPU kernels: single-pass WS/OS switching-activity profiling.

Replaces the host-side pipeline ``vertical_partial_sums`` (a materialized
(T, R, C) int64 cumsum) + XOR-popcount with kernels that, per grid cell:

  1. stream a block of activation steps through the resident ``(R, C)``
     weight tile,
  2. walk the reduction rows in a statically unrolled loop, carrying the
     running partial sum of every (step, column) as lo/hi int32 planes so
     the paper's 37-bit accumulations stay exact without 64-bit arithmetic
     (the VPU has none),
  3. XOR each time step against its predecessor, popcount under the
     bus-width mask, and
  4. reduce the counts to one int32 total per grid cell.

The (T, R, C) partial-sum tensor therefore never exists anywhere — not in
host memory, not in HBM, not in VMEM: each (T_block, C) plane of row r is
produced, toggled against, and overwritten by row r + 1.

TPU layout rules shape the code. Reduction row r is read as a static lane
slice of the activation block and a static sublane slice of the weight tile
(Mosaic lowers no dynamic lane slicing). Per-cell totals leave the kernel
lane-dense: a grid axis of up to ``LANE`` consecutive cells shares one
(1, 1, n) output block and cell j writes lane j (a rank-1 or (1, 1) block
would break the (8, 128) tiling rule).

Exact 64-bit partial sums from int32 lanes
------------------------------------------
Every int16 x int16 product fits int32. The running sum adds each product
to the lo plane (wrapping mod 2^32) and carries into the hi plane on
unsigned overflow, plus the product's sign extension — exact mod 2^64. A
bus of ``bits`` <= 32 sees only the lo plane, so those kernels skip the hi
plane. Bus toggles on a ``bits``-wide two's-complement bus are popcounts of
the XORed planes under a static (lo_mask, hi_mask) split — exact for bits
in [1, 64].

The same jnp helpers below are shared by the jitted XLA renderings in
ops.py and batch.py (used when no TPU is attached), so both engines are one
algorithm.

Output-stationary profiling needs no partial-sum machinery at all — both OS
buses carry raw operand streams — so its kernels are the lighter
``operand_stream_toggles_pallas`` (per-GEMM, time-blocked with a VMEM seed
carry) and ``stream_strips_toggles_pallas`` (batched seeded windows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitops import popcount_u32 as _popcount_u32

# Upper bound on block_t * rows * cols: bounds each grid cell's toggle count
# at ~2^20 * 128 bits, far below int32 overflow.
DEFAULT_BLOCK_BUDGET = 1 << 20
# Upper bound on one live (block_t, cols) int32 plane, lanes padded to a
# multiple of LANE: 256 KiB. The WS kernels keep about a dozen such planes
# live, well inside the TPU's default scoped VMEM.
PLANE_VMEM_ELEMS = 1 << 16
MAX_BLOCK_T = 512
MIN_BLOCK_T = 8
LANE = 128  # TPU vector lane count
# Stream lanes per grid cell of the per-GEMM operand-stream kernel.
STREAM_LANE_BLOCK = 8 * LANE
# Tasks per pallas_call of the task kernel: its three prefetched (P,) int32
# metadata arrays live in SMEM (1 MiB on v5e).
MAX_CALL_TASKS = 1 << 14

__all__ = [
    "DEFAULT_BLOCK_BUDGET",
    "choose_block_t",
    "bus_masks",
    "partial_sum_planes",
    "planes_toggles",
    "value32_toggles",
    "activity_profile_pallas",
    "activity_profile_pallas_tasks",
    "operand_stream_toggles_pallas",
    "stream_strips_toggles_pallas",
]


def choose_block_t(rows: int, cols: int, budget: int = DEFAULT_BLOCK_BUDGET) -> int:
    """Time-block size: as many steps as the element and VMEM budgets allow,
    8-aligned."""
    lanes = -(-max(cols, 1) // LANE) * LANE
    bt = min(budget // max(rows * cols, 1), PLANE_VMEM_ELEMS // lanes)
    bt = max(MIN_BLOCK_T, min(MAX_BLOCK_T, bt))
    return bt - (bt % MIN_BLOCK_T)


def bus_masks(bits: int) -> tuple[int, int]:
    """(lo_mask, hi_mask) selecting the low ``bits`` of a 64-bit lo/hi pair."""
    if not 1 <= bits <= 64:
        raise ValueError("bus width must be in [1, 64]")
    if bits >= 64:
        return 0xFFFFFFFF, 0xFFFFFFFF
    if bits >= 32:
        return 0xFFFFFFFF, (1 << (bits - 32)) - 1
    return (1 << bits) - 1, 0


def partial_sum_planes(
    a_block: jnp.ndarray, w_tile: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact 64-bit WS partial sums S[t, r, c] = sum_{r'<=r} a[t,r']*w[r',c].

    ``a_block`` is (BT, R) int32, ``w_tile`` is (R, C) int32; products must
    fit int32 (guaranteed for int16-range operands). Returns (s_lo, s_hi)
    int32 planes holding S mod 2^64.
    """
    p = a_block[:, :, None] * w_tile[None, :, :]
    p_lo = p & jnp.int32(0xFFFF)
    p_hi = p >> jnp.int32(16)  # arithmetic: p == p_hi * 2^16 + p_lo exactly
    acc_lo = jnp.cumsum(p_lo, axis=1)  # <= R * 0xffff, exact in int32
    acc_hi = jnp.cumsum(p_hi, axis=1)  # |.| <= R * 2^15, exact in int32
    # Reconstruct acc_hi * 2^16 + acc_lo as 64-bit lo/hi planes (mod 2^64).
    shifted = acc_hi << jnp.int32(16)
    s_lo = shifted + acc_lo
    carry = (s_lo.astype(jnp.uint32) < shifted.astype(jnp.uint32)).astype(jnp.int32)
    s_hi = (acc_hi >> jnp.int32(16)) + carry
    return s_lo, s_hi


def planes_toggles(
    s_lo: jnp.ndarray,
    s_hi: jnp.ndarray,
    p_lo: jnp.ndarray,
    p_hi: jnp.ndarray,
    bits: int,
) -> jnp.ndarray:
    """Per-element bit flips between two lo/hi-plane values on a ``bits`` bus."""
    lo_m, hi_m = bus_masks(bits)
    cnt = _popcount_u32((s_lo ^ p_lo).astype(jnp.uint32) & jnp.uint32(lo_m))
    if hi_m:
        cnt = cnt + _popcount_u32((s_hi ^ p_hi).astype(jnp.uint32) & jnp.uint32(hi_m))
    return cnt.astype(jnp.int32)


def value32_toggles(cur: jnp.ndarray, prev: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Bit flips between int32 values on a ``bits``-wide two's-complement bus.

    For bits > 32 the bus bits above 31 are sign-extension copies: they all
    flip together iff the sign bit flips.
    """
    x = cur ^ prev
    if bits <= 32:
        lo_m, _ = bus_masks(bits)
        return _popcount_u32(x.astype(jnp.uint32) & jnp.uint32(lo_m)).astype(jnp.int32)
    base = _popcount_u32(x.astype(jnp.uint32)).astype(jnp.int32)
    sign_flip = (x >> jnp.int32(31)) & jnp.int32(1)
    return base + sign_flip * jnp.int32(bits - 32)


def _add_row(run_lo, run_hi, prod, bits: int):
    """Add one reduction row's int32 products to the running lo/hi planes
    (exact mod 2^64; the hi plane is skipped for buses <= 32 bits, which
    see only the mod-2^32 lo plane)."""
    new_lo = run_lo + prod
    if bits <= 32:
        return new_lo, run_hi
    carry = (new_lo.astype(jnp.uint32) < run_lo.astype(jnp.uint32)).astype(jnp.int32)
    return new_lo, run_hi + (prod >> jnp.int32(31)) + carry


def _store_lane(o_ref, j, value) -> None:
    """Write scalar ``value`` into lane ``j`` of a (1, 1, n) output block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 2)
    o_ref[...] = jnp.where(lane == j, value, o_ref[...])


_CELL_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@functools.partial(
    jax.jit,
    static_argnames=(
        "rows", "cols", "k", "b_h", "b_v", "block_t", "interpret",
    ),
)
def activity_profile_pallas(
    a_pad: jnp.ndarray,
    w_pad: jnp.ndarray,
    *,
    rows: int,
    cols: int,
    k: int,
    b_h: int,
    b_v: int,
    block_t: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused toggle totals for every weight tile of a WS GEMM, in one pass.

    ``a_pad`` is (T_pad, K_pad) int32 — T edge-padded (replicated last row:
    zero extra toggles), K zero-padded to a multiple of ``rows``. ``w_pad``
    is (K_pad, N_pad) int32, zero-padded. ``k`` is the true (unpadded)
    reduction depth: K-padding rows would repeat the previous row's
    vertical count and are gated out; zero-padded K lanes and N columns
    toggle nothing. Totals are bit-exact vs. the unpadded numpy oracle.

    The operands are regrouped into (k_tiles, T_pad, rows) strips and
    (tiles, rows, cols) weight tiles so every block spans whole minor
    dimensions. Grid: (tile, t-block); the last partial-sum row of the
    previous t-block rides in VMEM scratch.

    Returns per-grid-cell int32 partials ``(h_out, v_out)`` of shape
    (num_tiles, 1, num_t_blocks); the caller reduces them in int64. Each
    cell's count is bounded by block_t*rows*cols*(64+b_h) < 2^31 via
    choose_block_t.
    """
    t_pad, k_pad = a_pad.shape
    n_pad = w_pad.shape[1]
    if t_pad % block_t or k_pad % rows or n_pad % cols:
        raise ValueError(
            f"padded shapes {(t_pad, k_pad, n_pad)} not multiples of "
            f"{(block_t, rows, cols)}"
        )
    k_tiles = k_pad // rows
    n_tiles = n_pad // cols
    num_tiles = k_tiles * n_tiles
    num_tb = t_pad // block_t
    a_strips = a_pad.reshape(t_pad, k_tiles, rows).transpose(1, 0, 2)
    w_tiles = (
        w_pad.reshape(k_tiles, rows, n_tiles, cols)
        .transpose(0, 2, 1, 3)
        .reshape(num_tiles, rows, cols)
    )

    def kernel(a_ref, w_ref, h_ref, v_ref, prev_lo, prev_hi, prev_a):
        p = pl.program_id(0)
        j = pl.program_id(1)
        later = j > 0  # the first t-block has no predecessor transition
        valid_r = jnp.minimum(rows, k - (p // n_tiles) * rows)

        a = a_ref[0]  # (block_t, rows)
        h = jnp.sum(value32_toggles(a[1:], a[:-1], b_h))
        h_edge = jnp.sum(value32_toggles(a[:1], prev_a[...], b_h))
        prev_a[...] = a[-1:]

        run_lo = run_hi = jnp.zeros((block_t, cols), jnp.int32)
        acc = jnp.zeros((block_t - 1, cols), jnp.int32)
        edge = jnp.zeros((1, cols), jnp.int32)
        for r in range(rows):
            prod = a_ref[0, :, r : r + 1] * w_ref[0, r : r + 1, :]
            run_lo, run_hi = _add_row(run_lo, run_hi, prod, b_v)
            live = r < valid_r
            acc += jnp.where(
                live,
                planes_toggles(run_lo[1:], run_hi[1:], run_lo[:-1], run_hi[:-1], b_v),
                0,
            )
            edge += jnp.where(
                live,
                planes_toggles(
                    run_lo[:1], run_hi[:1],
                    prev_lo[r : r + 1, :], prev_hi[r : r + 1, :], b_v,
                ),
                0,
            )
            prev_lo[r : r + 1, :] = run_lo[-1:]
            prev_hi[r : r + 1, :] = run_hi[-1:]
        v = jnp.sum(acc) + jnp.where(later, jnp.sum(edge), 0)
        _store_lane(h_ref, j, h + jnp.where(later, h_edge, 0))
        _store_lane(v_ref, j, v)

    out_spec = pl.BlockSpec((1, 1, num_tb), lambda p, j: (p, 0, 0))
    out_shape = jax.ShapeDtypeStruct((num_tiles, 1, num_tb), jnp.int32)
    return pl.pallas_call(
        kernel,
        name="activity_profile_pallas",
        grid=(num_tiles, num_tb),
        in_specs=[
            pl.BlockSpec((1, block_t, rows), lambda p, j: (p // n_tiles, j, 0)),
            pl.BlockSpec((1, rows, cols), lambda p, j: (p, 0, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        scratch_shapes=[
            pltpu.VMEM((rows, cols), jnp.int32),
            pltpu.VMEM((rows, cols), jnp.int32),
            pltpu.VMEM((1, rows), jnp.int32),
        ],
        compiler_params=_CELL_SEMANTICS,
        interpret=interpret,
    )(a_strips, w_tiles)


@functools.partial(jax.jit, static_argnames=("bits", "block_t", "interpret"))
def operand_stream_toggles_pallas(
    x_pad: jnp.ndarray,
    *,
    bits: int,
    block_t: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Toggle partials for a bundle of independent operand lane streams.

    The OS dataflow streams OPERANDS on both array axes — per-lane value
    sequences with no cross-lane arithmetic — so its per-GEMM profile needs
    only this kernel: ``x_pad`` is (T_pad, L) int32, one stream per column,
    T edge-padded to a ``block_t`` multiple (replicated values toggle zero
    bits).  Grid: (lane block, time block); lanes beyond
    ``STREAM_LANE_BLOCK`` are split into zero-padded blocks (constant lanes
    toggle nothing), and the previous time block's last row is carried in
    VMEM scratch so cross-block transitions count exactly once.  Returns
    (num_lane_blocks, 1, num_t_blocks) int32 partials, each bounded by
    block_t * STREAM_LANE_BLOCK * 64 < 2^31; the caller reduces in int64.
    """
    t_pad, lanes = x_pad.shape
    if t_pad % block_t:
        raise ValueError(f"padded stream length {t_pad} not a multiple of {block_t}")
    num_tb = t_pad // block_t
    block_l = lanes if lanes <= STREAM_LANE_BLOCK else STREAM_LANE_BLOCK
    x_pad = jnp.pad(x_pad, ((0, 0), (0, (-lanes) % block_l)))
    num_lb = x_pad.shape[1] // block_l

    def kernel(x_ref, o_ref, prev_x):
        j = pl.program_id(1)
        x = x_ref[...]  # (block_t, block_l)
        inner = jnp.sum(value32_toggles(x[1:], x[:-1], bits))
        edge = jnp.sum(value32_toggles(x[:1], prev_x[...], bits))
        prev_x[...] = x[-1:]
        _store_lane(o_ref, j, inner + jnp.where(j > 0, edge, 0))

    return pl.pallas_call(
        kernel,
        name="operand_stream_toggles_pallas",
        grid=(num_lb, num_tb),
        in_specs=[pl.BlockSpec((block_t, block_l), lambda i, j: (j, i))],
        out_specs=pl.BlockSpec((1, 1, num_tb), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_lb, 1, num_tb), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_l), jnp.int32)],
        compiler_params=_CELL_SEMANTICS,
        interpret=interpret,
    )(x_pad)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def stream_strips_toggles_pallas(
    strips: jnp.ndarray,
    *,
    bits: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-strip toggle totals for STACKED seeded stream windows.

    The batch pipeline flattens OS operand streams (and WS horizontal
    streams) into independent (t_seg + 1, lanes) windows whose row 0 seeds
    the cross-window transition (see ``batch.segment_strips``); each grid
    cell toggles one window, and ``LANE`` consecutive cells share one
    lane-dense output row.  Returns (S,) int32 totals, each bounded by
    t_seg * lanes * 64 < 2^31 by the segment budget; callers reduce int64.
    """
    num_strips, t_seg1, lanes = strips.shape
    groups = -(-num_strips // LANE)

    def kernel(s_ref, o_ref):
        s = s_ref[0]  # (t_seg + 1, lanes)
        _store_lane(o_ref, pl.program_id(1), jnp.sum(value32_toggles(s[1:], s[:-1], bits)))

    out = pl.pallas_call(
        kernel,
        name="stream_strips_toggles_pallas",
        grid=(groups, LANE),
        in_specs=[
            # cells past the last strip re-read it; their lanes are dropped
            pl.BlockSpec(
                (1, t_seg1, lanes),
                lambda g, j: (jnp.minimum(g * LANE + j, num_strips - 1), 0, 0),
            )
        ],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda g, j: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((groups, 1, LANE), jnp.int32),
        compiler_params=_CELL_SEMANTICS,
        interpret=interpret,
    )(strips)
    return out.reshape(-1)[:num_strips]


@functools.partial(
    jax.jit,
    static_argnames=("rows", "cols", "b_v", "interpret"),
)
def activity_profile_pallas_tasks(
    strips: jnp.ndarray,
    w_tiles: jnp.ndarray,
    strip_ids: jnp.ndarray,
    w_ids: jnp.ndarray,
    valid_r: jnp.ndarray,
    *,
    rows: int,
    cols: int,
    b_v: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Vertical-bus toggles for a STACKED segment-task batch (multi-GEMM).

    The batch pipeline (`repro.kernels.activity_profile.batch`) flattens
    many GEMMs into fixed-shape segment tasks; this kernel runs one task
    per grid cell. Task metadata rides in scalar-prefetch arrays so the
    BlockSpec index maps can route each cell to its operands: ``strips`` is
    (S, t_seg + 1, rows) seeded stream windows, ``w_tiles`` (W, rows, cols),
    ``strip_ids``/``w_ids``/``valid_r`` (P,) int32. Each cell walks the
    reduction rows carrying the (t_seg + 1, cols) partial-sum lo/hi planes —
    the (T, R, C) tensor never exists, VMEM holds one strip window + one
    weight tile + the plane carries. K-padding rows (r >= valid_r) would
    duplicate the previous row's count and are gated out; zero-padded w
    columns toggle nothing by construction; valid_r == 0 turns dummy
    padding tasks off. Tasks run in pallas_calls of at most
    ``MAX_CALL_TASKS`` (SMEM holds each call's metadata), ``LANE`` tasks
    per lane-dense output row.
    Returns (P,) int32 totals; the caller reduces in int64 (each total <=
    t_seg*rows*cols*64 < 2^27 by the choose_block_t budget). Horizontal
    counts are per-strip, not per-task, and run in the sibling XLA strips
    pass (a trivial fraction of the work).
    """
    num_tasks = strip_ids.shape[0]
    t_seg1 = strips.shape[1]
    per_call = min(MAX_CALL_TASKS, -(-num_tasks // LANE) * LANE)
    n_calls = -(-num_tasks // per_call)
    meta = jnp.stack([strip_ids, w_ids, valid_r]).astype(jnp.int32)
    # zero padding routes to task 0's operands with valid_r == 0: counts 0
    meta = jnp.pad(meta, ((0, 0), (0, n_calls * per_call - num_tasks)))
    meta = meta.reshape(3, n_calls, per_call)

    def kernel(sid_ref, wid_ref, vr_ref, a_ref, w_ref, v_ref):
        g = pl.program_id(0)
        j = pl.program_id(1)
        vr = vr_ref[g * LANE + j]
        run_lo = run_hi = jnp.zeros((t_seg1, cols), jnp.int32)
        acc = jnp.zeros((t_seg1 - 1, cols), jnp.int32)
        for r in range(rows):
            prod = a_ref[0, :, r : r + 1] * w_ref[0, r : r + 1, :]
            run_lo, run_hi = _add_row(run_lo, run_hi, prod, b_v)
            acc += jnp.where(
                r < vr,
                planes_toggles(run_lo[1:], run_hi[1:], run_lo[:-1], run_hi[:-1], b_v),
                0,
            )
        _store_lane(v_ref, j, jnp.sum(acc))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(per_call // LANE, LANE),
        in_specs=[
            pl.BlockSpec(
                (1, t_seg1, rows),
                lambda g, j, sid, wid, vr: (sid[g * LANE + j], 0, 0),
            ),
            pl.BlockSpec(
                (1, rows, cols),
                lambda g, j, sid, wid, vr: (wid[g * LANE + j], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda g, j, sid, wid, vr: (g, 0, 0)),
    )
    call = pl.pallas_call(
        kernel,
        name="activity_profile_pallas_tasks",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((per_call // LANE, 1, LANE), jnp.int32),
        compiler_params=_CELL_SEMANTICS,
        interpret=interpret,
    )
    out = [call(*meta[:, c], strips, w_tiles).reshape(-1) for c in range(n_calls)]
    return jnp.concatenate(out)[:num_tasks]
