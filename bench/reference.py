"""Plain references for the two device layers of a ``codesign`` answer.

1. Activity profiling. ``toggle_counts`` counts the bus toggles of one
   profiling job in plain numpy: the WS partial sums as an explicit int64
   prefix sum down each weight tile's rows, the OS buses as the raw operand
   streams along K, XOR and popcount at the bus width. Its operands come
   from ``synth_operands``, which rebuilds them from the job's clipped dims,
   bits and operand density by the documented generator (DESIGN.md
   Serving-workloads): nothing of the program's synthesis is called. The
   program's counts must equal it exactly.

2. The J/op program. ``jop_plain_gap`` compares a seeded sample of the
   answer's (GEMM, layout, point) cells, and its best and regime cells,
   with ``bench/plain_jop.py``: a float64 pricing written from the model's
   equations that imports nothing of the program. ``price_f64`` is a second
   witness over every cell of the grid: the program's own float64 numpy
   path (``evaluate_fleet_objective(use_jit=False)``) on freshly lowered
   tables, which catches float32 faults of the device program but shares
   the program's equations and lowering.

The controls put the reference in the program's place one precision lower:
the WS partial sums accumulated in float32 instead of exact integers, and
the J/op program computed in bfloat16 instead of float32. Each must come
out as not correct; ``bench/control.py`` reads them on the chip.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib

import numpy as np

from bench import plain_jop


def _toggles(prev: np.ndarray, nxt: np.ndarray, bits: int) -> int:
    mask = np.uint64((1 << bits) - 1) if bits < 64 else np.uint64(2**64 - 1)
    x = (prev.astype(np.int64) ^ nxt.astype(np.int64)).view(np.uint64) & mask
    return int(np.bitwise_count(x).sum())


def toggle_counts(a, w, rows, cols, b_h, b_v, dataflow, *, accumulate=np.int64):
    """(h_toggles, v_toggles, h_transitions, v_transitions) of one GEMM.

    WS: array rows hold K, columns hold N; a tile's horizontal bus carries
    the A columns of its K slice over M, its vertical bus the running sum
    down its rows. OS: row r streams an A row over K, column c a W column
    over K. ``accumulate=np.float32`` is the control's precision.
    """
    a = np.asarray(a, np.int64)
    w = np.asarray(w, np.int64)
    m, k = a.shape
    n = w.shape[1]
    m_tiles, n_tiles = -(-m // rows), -(-n // cols)
    if dataflow == "OS":
        h = _toggles(a[:, :-1], a[:, 1:], b_h) if k > 1 else 0
        v = _toggles(w[:-1], w[1:], b_v) if k > 1 else 0
        return h * n_tiles, v * m_tiles, max(k - 1, 0) * m * n_tiles, max(k - 1, 0) * n * m_tiles
    if dataflow != "WS":
        raise ValueError(f"unknown dataflow {dataflow!r}")
    h = v = 0
    for k0 in range(0, k, rows):
        at, wt = a[:, k0 : k0 + rows], w[k0 : k0 + rows]
        if m < 2:
            continue
        h += _toggles(at[:-1], at[1:], b_h)
        prod = at[:, :, None] * wt[None, :, :]  # (M, rows, N)
        if accumulate is np.int64:
            psum = np.cumsum(prod, axis=1)
        else:
            psum = np.rint(np.cumsum(prod.astype(accumulate), axis=1, dtype=accumulate))
            psum = psum.astype(np.int64)
        v += _toggles(psum[:-1], psum[1:], b_v)
    return h * n_tiles, v, max(m - 1, 0) * k * n_tiles, max(m - 1, 0) * k * n


def program_counts(p) -> tuple[int, int, int, int]:
    """The program's integer counts back out of its profile (activities
    are integer ratios held in float64 far below 2**53)."""
    return (
        round(p.a_h * p.h_transitions * p.b_h),
        round(p.a_v * p.v_transitions * p.b_v),
        int(p.h_transitions),
        int(p.v_transitions),
    )


def synth_operands(m: int, k: int, n: int, bits: int, density) -> tuple[np.ndarray, np.ndarray]:
    """The int operands of one profiling job, from its clipped (m, k, n),
    operand width and density: seeded by the first four bytes (little
    endian) of sha256 over ``"m|k|n|density"``; activations a ReLU'd unit
    Gaussian, zeroed outside a ``density`` mask when one is given, weights
    Gaussian with standard deviation 1/sqrt(k), each quantised symmetric
    per tensor to signed ``bits``-bit integers."""
    seed = int.from_bytes(hashlib.sha256(f"{m}|{k}|{n}|{density}".encode()).digest()[:4], "little")
    rng = np.random.default_rng(seed)
    a = np.maximum(rng.normal(0.0, 1.0, size=(m, k)), 0.0)
    if density is not None:
        a = np.where(rng.random((m, k)) < density, a, 0.0)
    w = rng.normal(0.0, 1.0 / np.sqrt(k), size=(k, n))

    def quantise(x):
        qmax = float(2 ** (bits - 1) - 1)
        amax = float(np.max(np.abs(x))) if x.size else 0.0
        scale = amax / qmax if amax > 0 else 1.0
        return np.clip(np.rint(x / scale), -qmax, qmax).astype(np.int64)

    return quantise(a), quantise(w)


def job_densities(jobset) -> dict:
    """(GEMM name, clipped shape) -> operand density, from the answer's job
    set: a profiling job carries its GEMM's name and clipped shape."""
    out: dict = {}
    for g, d in zip(jobset.gemms, jobset.densities):
        out.setdefault(g.name, []).append(((g.m, g.k, g.n), d))
    return out


def _density_of(job, densities: dict):
    m, k, n = job.shape
    found = {d for (gm, gk, gn), d in densities.get(job.name, [])
             if (min(gm, m), min(gk, k), min(gn, n)) == (m, k, n)}
    if len(found) != 1:
        raise LookupError(f"job {job.name!r} {job.shape}: densities {found}")
    return found.pop()


def count_mismatches(
    batches, densities: dict, memo: dict, *, accumulate=np.int64, program=True
) -> tuple[int, int]:
    """(jobs whose counts differ from the reference, jobs compared).

    ``batches`` are the (jobs, profiles) pairs an answer's profiling ran,
    ``densities`` the answer's ``job_densities``. With ``program=False``
    the reference at ``accumulate`` precision takes the program's place
    (the control).
    """
    bad = total = 0
    for jobs, profiles in batches:
        for job, prof in zip(jobs, profiles):
            m, k, n = job.shape
            key = (job.rows, job.cols, job.b_h, job.b_v, job.dataflow, m, k, n,
                   _density_of(job, densities))
            if key not in memo:
                a, w = synth_operands(m, k, n, job.b_h, key[-1])
                memo[key] = (a, w, toggle_counts(a, w, job.rows, job.cols, job.b_h,
                                                 job.b_v, job.dataflow))
            a, w, want = memo[key]
            if program:
                got = program_counts(prof) if prof is not None else None
            else:
                got = toggle_counts(
                    a, w, job.rows, job.cols, job.b_h, job.b_v, job.dataflow,
                    accumulate=accumulate,
                )
            bad += got != want
            total += 1
    return bad, total


def price_f64(pricing):
    """The answer's pricing inputs priced by the float64 host path."""
    from repro.core.objective import evaluate_fleet_objective
    from repro.layout.coeffs import clear_coeff_cache

    clear_coeff_cache()  # lower afresh: no table of the timed run is reused
    args, kwargs = pricing
    return evaluate_fleet_objective(*args, **{**kwargs, "use_jit": False, "sweep": None})


def jop_gap(result, ref) -> float:
    """Widest relative gap between the answer and the float64 pricing of
    the inputs its question gave the J/op program, over every finite cell
    of J/token (layout, point) and of J/op per GEMM (GEMM, layout, point).
    The best cell and the regime cells are argmins of these blocks, so each
    lies within twice this gap of the float64 optimum. A cell finite on one
    side only, or a block of another shape, reads infinity."""
    gap = 0.0
    for got, want in (
        (result.eval.j_per_token_robust, ref.j_per_token_robust),
        (result.eval.j_per_mac, ref.j_per_mac),
    ):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        fin = np.isfinite(want)
        if got.shape != want.shape or not np.array_equal(fin, np.isfinite(got)):
            return float("inf")
        gap = max(gap, float(np.max(np.abs(got[fin] - want[fin]) / np.abs(want[fin]))))
    return gap


def jop_plain_gap(result, pricing, space, rng, n_cells: int) -> float:
    """Widest relative gap between the answer and ``plain_jop`` over the
    answer's best cell, its decode and prefill cells and ``n_cells``
    (layout, point) cells drawn by ``rng``: at each, J/op of every GEMM and
    J/token. A cell finite on one side only reads infinity."""
    args, kwargs = pricing
    a_h, a_v, gemms = np.asarray(args[1]), np.asarray(args[2]), args[3]
    weights = np.asarray(kwargs["weights"], np.float64)
    layouts = tuple(kwargs["layouts"])
    shape = np.asarray(result.eval.j_per_token_robust).shape
    picked = [result.best_cell, result.regime_cell("decode"), result.regime_cell("prefill")]
    flat = rng.choice(shape[0] * shape[1], size=min(n_cells, shape[0] * shape[1]), replace=False)
    picked += [tuple(int(i) for i in np.unravel_index(c, shape)) for c in sorted(flat)]
    dims = [(g.m, g.k, g.n) for g in gemms]
    gap = 0.0
    for li, pi in dict.fromkeys(picked):
        pt = plain_jop.point_of(space, pi)
        want = plain_jop.price_cell(layouts[li], pt, dims, weights, a_h[:, pi], a_v[:, pi])
        want_tok = plain_jop.fleet_j_per_mac(want, weights) * float(kwargs["macs_per_token"])
        got = np.append(np.asarray(result.eval.j_per_mac, np.float64)[:, li, pi],
                        float(result.eval.j_per_token_robust[li, pi]))
        want = np.append(want, want_tok)
        fin = np.isfinite(want)
        if not np.array_equal(fin, np.isfinite(got)):
            return float("inf")
        if fin.any():
            gap = max(gap, float(np.max(np.abs(got[fin] - want[fin]) / np.abs(want[fin]))))
    return gap


@contextlib.contextmanager
def bf16_jop_program():
    """The control of the J/op layer: the same jitted program with every
    floating input cast to bfloat16, its outputs back to float32."""
    import jax
    import jax.numpy as jnp

    from repro.layout import power

    orig = power._jitted_coeff_eval

    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.bfloat16)
        return x

    @functools.lru_cache(maxsize=None)
    def lowered(rep_idx, nb, nn, donate):
        fn = orig(rep_idx, nb, nn, donate)

        @jax.jit
        def run(*args):
            out = fn(*(cast(x) for x in args))
            return jax.tree.map(lambda y: y.astype(jnp.float32), out)

        return run

    power._jitted_coeff_eval = lowered
    try:
        yield
    finally:
        power._jitted_coeff_eval = orig
