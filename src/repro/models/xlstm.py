"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM (scalar).

mLSTM is a gated linear-attention recurrence with per-head scalar gates:

    C_t = f_t C_{t-1} + i_t k_t v_t^T        (matrix memory, dh x dh)
    n_t = f_t n_{t-1} + i_t k_t              (normalizer)
    h_t = (C_t^T q_t) / max(|n_t . q_t|, 1)

Training/prefill uses the chunkwise-parallel form (intra-chunk quadratic +
inter-chunk carried state) — O(S) memory, sub-quadratic compute, which is why
xlstm-1.3b runs the long_500k cell. Simplification vs the paper: sigmoid
input/forget gates (bounded, stabilizer-free) instead of exp-input gating with
running max-state; documented in DESIGN.md §Arch-applicability.

sLSTM keeps the paper's exponential gating with the m-state stabilizer and a
per-head block-diagonal recurrent matrix; it is inherently sequential
(lax.scan over time).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_param, ones_param, zeros_param
from repro.parallel.sharding import shard_hint


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def mlstm_init(key, cfg, stack: int) -> tuple[dict, dict]:
    d = cfg.d_model
    di = int(cfg.xlstm_proj_factor * d)  # pre-up-projection inner width
    h = cfg.num_heads
    keys = jax.random.split(key, 8)
    p, a = {}, {}
    dh = di // h
    p["w_up"], a["w_up"] = dense_param(keys[0], (d, 2 * di), ("embed", "inner"), stack=stack)
    # block-diagonal (per-head) q/k/v projections, as in the xLSTM reference
    p["wq"], a["wq"] = dense_param(keys[1], (h, dh, dh), ("heads", None, None), stack=stack, scale=dh ** -0.5)
    p["wk"], a["wk"] = dense_param(keys[2], (h, dh, dh), ("heads", None, None), stack=stack, scale=dh ** -0.5)
    p["wv"], a["wv"] = dense_param(keys[3], (h, dh, dh), ("heads", None, None), stack=stack, scale=dh ** -0.5)
    p["w_igate"], a["w_igate"] = dense_param(keys[4], (di, h), ("inner", "heads"), stack=stack)
    p["w_fgate"], a["w_fgate"] = dense_param(keys[5], (di, h), ("inner", "heads"), stack=stack)
    p["b_fgate"], a["b_fgate"] = ones_param((h,), ("heads",), stack=stack)  # bias>0: long memory
    p["out_norm"], a["out_norm"] = ones_param((di,), ("inner",), stack=stack)
    p["w_down"], a["w_down"] = dense_param(keys[6], (di, d), ("inner", "embed"), stack=stack)
    return p, a


def _mlstm_chunk(q, k, v, li, lf, c0, n0):
    """One chunk of the chunkwise-parallel mLSTM.

    q/k/v: (B, H, c, dh); li/lf: (B, H, c) log input/forget gates.
    c0: (B, H, dh, dh); n0: (B, H, dh). Returns (h, c1, n1).
    """
    cum = jnp.cumsum(lf, axis=-1)  # log decay from chunk start (inclusive)
    # intra-chunk decay matrix: M[t, j] = exp(cum_t - cum_j + li_j), j <= t
    log_m = cum[..., :, None] - cum[..., None, :] + li[..., None, :]
    tri = jnp.tril(jnp.ones((q.shape[2], q.shape[2]), dtype=bool))
    m = jnp.where(tri[None, None], jnp.exp(log_m), 0.0)

    scale = q.shape[-1] ** -0.5
    qk = jnp.einsum("bhtd,bhjd->bhtj", q, k) * scale  # (B, H, c, c)
    w = qk * m
    intra = jnp.einsum("bhtj,bhjd->bhtd", w, v)
    decay_t = jnp.exp(cum)[..., None]  # (B, H, c, 1)
    inter = decay_t * jnp.einsum("bhtd,bhde->bhte", q * scale, c0)
    # normalizer: q.n_t = decay_t * (q.n0) + row-sum of the gated qk matrix
    qn = decay_t[..., 0] * jnp.einsum("bhtd,bhd->bht", q * scale, n0) + jnp.sum(
        w, axis=-1
    )
    denom = jnp.maximum(jnp.abs(qn), 1.0)[..., None]
    h = (intra + inter) / denom

    # carry updates: decay from t to chunk end
    total = cum[..., -1:]  # (B, H, 1)
    dec_end = jnp.exp(total - cum + li)  # (B, H, c) includes input gate
    c1 = jnp.exp(total)[..., None] * c0 + jnp.einsum(
        "bhtd,bhte,bht->bhde", k, v, dec_end
    )
    n1 = jnp.exp(total) * n0 + jnp.einsum("bhtd,bht->bhd", k, dec_end)
    return h, c1, n1


def _rms(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def mlstm_apply(p, x, cfg) -> jnp.ndarray:
    """Full-sequence mLSTM. x: (B, S, D)."""
    b, s, d = x.shape
    hh = cfg.num_heads
    di = int(cfg.xlstm_proj_factor * d)
    dh = di // hh
    dtype = x.dtype
    chunk = cfg.scan_chunk if s % cfg.scan_chunk == 0 else s
    nc = s // chunk

    up = x @ p["w_up"].astype(dtype)
    inner, z = jnp.split(up, 2, axis=-1)  # (B, S, di)
    inner = shard_hint(inner, "batch", None, "inner")  # full seq inside block
    inner_h = inner.reshape(b, s, hh, dh).transpose(0, 2, 1, 3)  # (B, H, S, dh)
    q = jnp.einsum("bhsd,hde->bhse", inner_h, p["wq"].astype(dtype))
    k = jnp.einsum("bhsd,hde->bhse", inner_h, p["wk"].astype(dtype))
    v = jnp.einsum("bhsd,hde->bhse", inner_h, p["wv"].astype(dtype))
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    li = jax.nn.log_sigmoid(inner @ p["w_igate"].astype(dtype)).astype(jnp.float32)
    lf = jax.nn.log_sigmoid(
        inner @ p["w_fgate"].astype(dtype) + p["b_fgate"].astype(dtype)
    ).astype(jnp.float32)
    li = li.transpose(0, 2, 1)  # (B, H, S)
    lf = lf.transpose(0, 2, 1)

    def step(carry, idx):
        c0, n0 = carry
        sl = lambda t, ax: jax.lax.dynamic_slice_in_dim(t, idx * chunk, chunk, axis=ax)
        h, c1, n1 = _mlstm_chunk(
            sl(q, 2), sl(k, 2), sl(v, 2), sl(li, 2), sl(lf, 2), c0, n0
        )
        return (c1, n1), h

    c0 = jnp.zeros((b, hh, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, hh, dh), jnp.float32)
    if nc == 1:
        h, _, _ = _mlstm_chunk(q, k, v, li, lf, c0, n0)
    else:
        _, hs = jax.lax.scan(jax.checkpoint(step), (c0, n0), jnp.arange(nc))
        # hs: (nc, B, H, chunk, dh) -> (B, H, S, dh)
        h = jnp.moveaxis(hs, 0, 2).reshape(b, hh, s, dh)

    h = h.transpose(0, 2, 1, 3).reshape(b, s, di).astype(dtype)
    h = _rms(h, p["out_norm"])
    h = h * jax.nn.silu(z)
    return h @ p["w_down"].astype(dtype)


def mlstm_cache_init(cfg, batch: int, stack: int, dtype) -> tuple[dict, dict]:
    hh = cfg.num_heads
    di = int(cfg.xlstm_proj_factor * cfg.d_model)
    dh = di // hh
    cache = {
        "C": jnp.zeros((stack, batch, hh, dh, dh), jnp.float32),
        "n": jnp.zeros((stack, batch, hh, dh), jnp.float32),
    }
    axes = {
        "C": ("layers", "batch", "heads", None, None),
        "n": ("layers", "batch", "heads", None),
    }
    return cache, axes


def mlstm_decode(p, x, cache, cfg) -> tuple[jnp.ndarray, dict]:
    """One-token mLSTM decode. x: (B, 1, D)."""
    b = x.shape[0]
    d = cfg.d_model
    hh = cfg.num_heads
    di = int(cfg.xlstm_proj_factor * d)
    dh = di // hh
    dtype = x.dtype

    up = x[:, 0] @ p["w_up"].astype(dtype)
    inner, z = jnp.split(up, 2, axis=-1)
    inner_h = inner.reshape(b, hh, dh)
    q = jnp.einsum("bhd,hde->bhe", inner_h, p["wq"].astype(dtype)).astype(jnp.float32)
    k = jnp.einsum("bhd,hde->bhe", inner_h, p["wk"].astype(dtype)).astype(jnp.float32)
    v = jnp.einsum("bhd,hde->bhe", inner_h, p["wv"].astype(dtype)).astype(jnp.float32)
    i_g = jax.nn.sigmoid(inner @ p["w_igate"].astype(dtype)).astype(jnp.float32)  # (B, H)
    f_g = jax.nn.sigmoid(
        inner @ p["w_fgate"].astype(dtype) + p["b_fgate"].astype(dtype)
    ).astype(jnp.float32)

    c1 = f_g[..., None, None] * cache["C"] + i_g[..., None, None] * (
        k[..., :, None] * v[..., None, :]
    )
    n1 = f_g[..., None] * cache["n"] + i_g[..., None] * k
    scale = dh ** -0.5
    num = jnp.einsum("bhde,bhd->bhe", c1, q * scale)
    qn = jnp.einsum("bhd,bhd->bh", n1, q * scale)
    h = num / jnp.maximum(jnp.abs(qn), 1.0)[..., None]
    h = h.reshape(b, di).astype(dtype)
    h = _rms(h, p["out_norm"])
    h = h * jax.nn.silu(z)
    out = (h @ p["w_down"].astype(dtype))[:, None, :]
    return out, {"C": c1, "n": n1}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def slstm_init(key, cfg, stack: int) -> tuple[dict, dict]:
    d = cfg.d_model
    hh = cfg.num_heads
    dh = d // hh
    keys = jax.random.split(key, 10)
    p, a = {}, {}
    for i, gate in enumerate(("z", "i", "f", "o")):
        p[f"w_{gate}"], a[f"w_{gate}"] = dense_param(
            keys[i], (d, d), ("embed", "inner"), stack=stack
        )
        p[f"r_{gate}"], a[f"r_{gate}"] = dense_param(
            keys[4 + i], (hh, dh, dh), ("heads", None, None), stack=stack, scale=dh ** -0.5
        )
        p[f"b_{gate}"], a[f"b_{gate}"] = zeros_param((d,), ("inner",), stack=stack)
    p["out_norm"], a["out_norm"] = ones_param((d,), ("embed",), stack=stack)
    ff = cfg.slstm_ff  # post-recurrence gated MLP
    p["w_ff_gate"], a["w_ff_gate"] = dense_param(keys[8], (d, ff), ("embed", "mlp"), stack=stack)
    p["w_ff_down"], a["w_ff_down"] = dense_param(keys[9], (ff, d), ("mlp", "embed"), stack=stack)
    return p, a


def slstm_apply(p, x, cfg) -> jnp.ndarray:
    """Full-sequence sLSTM (sequential scan over time). x: (B, S, D)."""
    b, s, d = x.shape
    hh = cfg.num_heads
    dh = d // hh
    dtype = x.dtype

    # precompute input contributions for all gates: (B, S, D) each
    pre = {
        g: (x @ p[f"w_{g}"].astype(dtype) + p[f"b_{g}"].astype(dtype)).astype(jnp.float32)
        for g in ("z", "i", "f", "o")
    }
    r = {g: p[f"r_{g}"].astype(jnp.float32) for g in ("z", "i", "f", "o")}

    def step(carry, t):
        c, n, h, m = carry  # (B, H, dh) x3, (B, H)
        rec = {g: jnp.einsum("bhd,hde->bhe", h, r[g]) for g in r}
        sl = lambda g: jax.lax.dynamic_slice_in_dim(pre[g], t, 1, axis=1)[:, 0].reshape(
            b, hh, dh
        )
        z = jnp.tanh(sl("z") + rec["z"])
        i_t = sl("i") + rec["i"]
        f_t = sl("f") + rec["f"]
        o = jax.nn.sigmoid(sl("o") + rec["o"])
        # exponential gating with per-(B, H, dh) log-stabilizer state m
        m_new = jnp.maximum(f_t + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(f_t + m - m_new)
        c_new = f_p * c + i_p * z
        n_new = f_p * n + i_p
        h_new = o * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, h_new, m_new), h_new

    zeros = jnp.zeros((b, hh, dh), jnp.float32)
    init = (zeros, zeros, zeros, zeros)
    _, hs = jax.lax.scan(step, init, jnp.arange(s))
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(dtype)
    h = _rms(h, p["out_norm"])
    # gated feed-forward (GELU-gate)
    ffh = jax.nn.gelu(h @ p["w_ff_gate"].astype(dtype))
    return ffh @ p["w_ff_down"].astype(dtype)


def slstm_cache_init(cfg, batch: int, stack: int, dtype) -> tuple[dict, dict]:
    hh = cfg.num_heads
    dh = cfg.d_model // hh
    shape = (stack, batch, hh, dh)
    cache = {k: jnp.zeros(shape, jnp.float32) for k in ("c", "n", "h", "m")}
    axes = {k: ("layers", "batch", "heads", None) for k in cache}
    return cache, axes


def slstm_decode(p, x, cache, cfg) -> tuple[jnp.ndarray, dict]:
    b = x.shape[0]
    d = cfg.d_model
    hh = cfg.num_heads
    dh = d // hh
    dtype = x.dtype
    c, n, h, m = cache["c"], cache["n"], cache["h"], cache["m"]
    rec = {
        g: jnp.einsum("bhd,hde->bhe", h, p[f"r_{g}"].astype(jnp.float32))
        for g in ("z", "i", "f", "o")
    }
    pre = {
        g: (x[:, 0] @ p[f"w_{g}"].astype(dtype) + p[f"b_{g}"].astype(dtype))
        .astype(jnp.float32)
        .reshape(b, hh, dh)
        for g in ("z", "i", "f", "o")
    }
    z = jnp.tanh(pre["z"] + rec["z"])
    i_t = pre["i"] + rec["i"]
    f_t = pre["f"] + rec["f"]
    o = jax.nn.sigmoid(pre["o"] + rec["o"])
    m_new = jnp.maximum(f_t + m, i_t)
    i_p = jnp.exp(i_t - m_new)
    f_p = jnp.exp(f_t + m - m_new)
    c_new = f_p * c + i_p * z
    n_new = f_p * n + i_p
    h_new = o * c_new / jnp.maximum(n_new, 1.0)
    out = h_new.reshape(b, d).astype(dtype)
    out = _rms(out, p["out_norm"])
    ffh = jax.nn.gelu(out @ p["w_ff_gate"].astype(dtype))
    out = (ffh @ p["w_ff_down"].astype(dtype))[:, None, :]
    return out, {"c": c_new, "n": n_new, "h": h_new, "m": m_new}
