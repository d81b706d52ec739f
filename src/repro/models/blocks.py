"""Transformer building blocks: GQA attention, dense MLP, routed MoE.

Each block exposes ``<block>_init(key, cfg, stack)`` returning parallel
(params, axes) pytrees — stacked over a leading 'layers' axis for scan — and
apply functions for full-sequence forward and single-token cached decode.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import (
    ACTIVATIONS,
    apply_rope,
    blockwise_attention,
    decode_attention,
    dense_attention,
    dense_param,
    mrope_angles,
    ones_param,
    rms_norm,
    rope_angles,
    zeros_param,
)
from repro.parallel.sharding import shard_hint


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attn_init(key, cfg, stack: int) -> tuple[dict, dict]:
    d = cfg.d_model
    hd = cfg.head_dim
    keys = jax.random.split(key, 8)
    p, a = {}, {}
    p["wq"], a["wq"] = dense_param(
        keys[0], (d, cfg.num_heads, hd), ("embed", "heads", None), stack=stack
    )
    p["wk"], a["wk"] = dense_param(
        keys[1], (d, cfg.num_kv_heads, hd), ("embed", "kv_heads", None), stack=stack
    )
    p["wv"], a["wv"] = dense_param(
        keys[2], (d, cfg.num_kv_heads, hd), ("embed", "kv_heads", None), stack=stack
    )
    p["wo"], a["wo"] = dense_param(
        keys[3], (cfg.num_heads, hd, d), ("heads", None, "embed"), stack=stack
    )
    if cfg.qkv_bias:
        p["bq"], a["bq"] = zeros_param((cfg.num_heads, hd), ("heads", None), stack=stack)
        p["bk"], a["bk"] = zeros_param(
            (cfg.num_kv_heads, hd), ("kv_heads", None), stack=stack
        )
        p["bv"], a["bv"] = zeros_param(
            (cfg.num_kv_heads, hd), ("kv_heads", None), stack=stack
        )
    if cfg.qk_norm:
        p["q_norm"], a["q_norm"] = ones_param((hd,), (None,), stack=stack)
        p["k_norm"], a["k_norm"] = ones_param((hd,), (None,), stack=stack)
    return p, a


def _qkv(p, x, cfg, cos, sin):
    """Project + (bias) + (qk-norm) + rope. x: (B, S, D) -> q/k/v (B, H, S, hd)."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)[None, :, None, :]
        k = k + p["bk"].astype(x.dtype)[None, :, None, :]
        v = v + p["bv"].astype(x.dtype)[None, :, None, :]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _rope_tables(cfg, positions):
    """positions: (B, S) int32, or (3, B, S) for M-RoPE archs."""
    if positions is None:
        return None, None
    if cfg.rope_kind == "none":
        return None, None
    if cfg.rope_kind == "mrope":
        return mrope_angles(positions, cfg.head_dim, cfg.mrope_sections, cfg.rope_theta)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta)


def attn_apply(p, x, cfg, positions) -> jnp.ndarray:
    """Full-sequence causal attention. x: (B, S, D)."""
    b, s, d = x.shape
    cos, sin = _rope_tables(cfg, positions)
    q, k, v = _qkv(p, x, cfg, cos, sin)
    # Megatron-SP style layout transition: the residual stream is
    # seq-sharded; attention internals run head-sharded over the FULL
    # sequence (explicit hints prevent SPMD from chasing the seq shard
    # through the GQA repeat / chunk reshapes — involuntary remat storms).
    q = shard_hint(q, "batch", "heads", None, None)
    k = shard_hint(k, "batch", "kv_heads", None, None)
    v = shard_hint(v, "batch", "kv_heads", None, None)
    rep = cfg.num_heads // cfg.num_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if s > cfg.attn_chunk:
        o = blockwise_attention(
            q, k, v, causal=True, window=cfg.window,
            q_chunk=cfg.attn_chunk, k_chunk=cfg.attn_chunk,
        )
    else:
        o = dense_attention(q, k, v, causal=True, window=cfg.window)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return shard_hint(out, "batch", "seq", "embed")


def attn_cache_init(cfg, batch: int, cache_len: int, stack: int, dtype) -> tuple[dict, dict]:
    """KV cache (+ per-slot position ring for SWA). Stacked over stages."""
    hd = cfg.head_dim
    shape = (stack, batch, cfg.num_kv_heads, cache_len, hd)
    axes = ("layers", "batch", "kv_heads", "cache_seq", None)
    cache = {
        "k": jnp.zeros(shape, dtype=dtype),
        "v": jnp.zeros(shape, dtype=dtype),
        "slot_pos": jnp.full((stack, cache_len), -1, dtype=jnp.int32),
    }
    caxes = {"k": axes, "v": axes, "slot_pos": ("layers", "cache_seq")}
    return cache, caxes


def attn_decode(p, x, cache, pos, cfg) -> tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, D); cache entries are per-stage slices
    (B, KV, S_cache, hd) / (S_cache,). ``pos`` is the new token's position."""
    b = x.shape[0]
    cache_len = cache["k"].shape[2]
    if cfg.rope_kind == "mrope":
        # decode: all three M-RoPE streams advance with the text position
        pos_arr = jnp.broadcast_to(pos, (3, b, 1)).astype(jnp.int32)
    else:
        pos_arr = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    cos, sin = _rope_tables(cfg, pos_arr)
    q, k_new, v_new = _qkv(p, x, cfg, cos, sin)

    if cfg.window is not None and cache_len == cfg.window:
        slot = (pos % cache_len).astype(jnp.int32)  # SWA ring buffer
    else:
        slot = jnp.minimum(pos, cache_len - 1).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, 0, slot, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, 0, slot, 0)
    )
    slot_pos = jax.lax.dynamic_update_slice(
        cache["slot_pos"], jnp.asarray(pos, jnp.int32).reshape(1), (slot,)
    )

    rep = cfg.num_heads // cfg.num_kv_heads
    qh = q  # (B, H, 1, hd)
    kv_heads = cfg.num_kv_heads
    scale = cfg.head_dim ** -0.5
    qg = qh.reshape(b, kv_heads, rep, cfg.head_dim)
    logits = (
        jnp.einsum("bgrk,bgsk->bgrs", qg.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    valid = slot_pos >= 0  # ring slots hold only in-window entries
    logits = jnp.where(valid[None, None, None, :], logits, -1.0e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bgrs,bgsk->bgrk", probs, v.astype(jnp.float32))
    o = o.reshape(b, cfg.num_heads, 1, cfg.head_dim).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": k, "v": v, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# Dense (SwiGLU) MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, stack: int, d_ff: int | None = None) -> tuple[dict, dict]:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    keys = jax.random.split(key, 3)
    p, a = {}, {}
    p["w_gate"], a["w_gate"] = dense_param(keys[0], (d, ff), ("embed", "mlp"), stack=stack)
    if cfg.gated_mlp:
        p["w_up"], a["w_up"] = dense_param(keys[1], (d, ff), ("embed", "mlp"), stack=stack)
    p["w_down"], a["w_down"] = dense_param(keys[2], (ff, d), ("mlp", "embed"), stack=stack)
    return p, a


def mlp_apply(p, x, cfg) -> jnp.ndarray:
    act = ACTIVATIONS[cfg.activation]
    h = act(x @ p["w_gate"].astype(x.dtype))
    if cfg.gated_mlp:
        h = h * (x @ p["w_up"].astype(x.dtype))
    # d_ff tensor-parallel, full seq (residual re-shards to SP afterwards)
    h = shard_hint(h, *(("batch", None, "mlp") if x.ndim == 3 else ("batch", "mlp")))
    return h @ p["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Routed MoE (gather/scatter dispatch — no dense one-hot einsum flops)
# ---------------------------------------------------------------------------


def moe_init(key, cfg, stack: int) -> tuple[dict, dict]:
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    keys = jax.random.split(key, 5)
    p, a = {}, {}
    p["router"], a["router"] = dense_param(keys[0], (d, e), ("embed", None), stack=stack)
    # dedicated logical axes: expert weights' FSDP/TP assignment is a perf
    # lever independent of the dense layers' (see §Perf — replicating them
    # over 'data' trades ~1.5 GiB HBM for zero per-layer FSDP gathers)
    p["w_gate"], a["w_gate"] = dense_param(
        keys[1], (e, d, ff), ("experts", "expert_embed", "expert_mlp"), stack=stack
    )
    p["w_up"], a["w_up"] = dense_param(
        keys[2], (e, d, ff), ("experts", "expert_embed", "expert_mlp"), stack=stack
    )
    p["w_down"], a["w_down"] = dense_param(
        keys[3], (e, ff, d), ("experts", "expert_mlp", "expert_embed"), stack=stack
    )
    if cfg.num_shared_experts:
        p["shared"], a["shared"] = mlp_init(
            keys[4], cfg, stack=stack, d_ff=ff * cfg.num_shared_experts
        )
    return p, a


def _dispatch_local(x_loc, expert_idx_loc, e: int, k_top: int, capacity: int, shards: int):
    """Per-shard (device-local) capacity dispatch. x_loc: (T_loc, D).

    Sort-based ranking, static local capacity, overflow dropped. Returns the
    local expert buffers reshaped to (shards, E*capacity/shards, D) — the
    PHYSICAL expert layout (replication groups split an expert's capacity
    rows contiguously, which is a free local reshape of the same linear
    buffer) — and the slot->buffer-row map for the combine gather. Runs
    unpartitioned (single device or inside shard_map), so the scatter never
    crosses devices.
    """
    t_loc, d = x_loc.shape
    eids = expert_idx_loc.reshape(-1)  # (T_loc*k,) slot-major
    tok_of_slot = jnp.arange(t_loc * k_top) // k_top
    sort_idx = jnp.argsort(eids)  # stable
    sorted_eids = eids[sort_idx]
    group_start = jnp.searchsorted(sorted_eids, jnp.arange(e))
    rank_sorted = jnp.arange(t_loc * k_top) - group_start[sorted_eids]
    rank = jnp.zeros_like(rank_sorted).at[sort_idx].set(rank_sorted)

    valid = rank < capacity
    dest = jnp.where(valid, eids * capacity + rank, e * capacity)  # overflow row
    gathered = x_loc[tok_of_slot]  # (T_loc*k, D)
    buf = jnp.zeros((e * capacity + 1, d), dtype=x_loc.dtype)
    buf = buf.at[dest].add(gathered * valid[:, None].astype(x_loc.dtype))
    return buf[:-1].reshape(shards, e * capacity // shards, d), dest


def _combine_local(expert_out_loc, dest, gate_vals_loc, k_top: int):
    """Inverse of _dispatch_local: gather slots back to (T_loc, D)."""
    d = expert_out_loc.shape[-1]
    flat = expert_out_loc.reshape(-1, d)  # same linear order dest indexes
    padded = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)])
    valid = (dest < flat.shape[0]).astype(flat.dtype)
    per_slot = padded[dest] * (gate_vals_loc.reshape(-1) * valid)[:, None].astype(
        flat.dtype
    )
    t_loc = gate_vals_loc.shape[0]
    return jnp.sum(per_slot.reshape(t_loc, k_top, d), axis=1)


def _token_partition(mesh, t: int, act_rules) -> tuple[str, ...] | None:
    """Mesh axes the flat token dim is sharded over (from the batch rule)."""
    from repro.parallel.sharding import spec_for_axes

    spec = spec_for_axes(("batch",), (t,), mesh, act_rules)
    entry = spec[0] if len(spec) else None
    if entry is None:
        return None
    return entry if isinstance(entry, tuple) else (entry,)


def moe_apply(p, x, cfg, dropless: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token-dispatch MoE. x: (B, S, D) -> (out, aux_loss).

    ``dropless=True`` sizes capacity at the worst case (T*k rows per expert)
    so no token is ever dropped — the serving/decode setting, where dropping
    would make cached decoding diverge from the prefill forward pass.

    Distribution strategy (the part XLA cannot infer): the dispatch scatter
    and combine gather are *device-local* (shard_map over the token shards),
    and only the dense (E, C, D) buffers cross devices — resharded from
    capacity-sharded to expert-sharded, which SPMD lowers to the expert-
    parallel all-to-all. A global scatter would instead be lowered by SPMD as
    a replicated (E*C, D) buffer per device (measured: 197 GiB temp for the
    mixtral train cell — see EXPERIMENTS.md §Perf).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel.sharding import active_act_rules, active_mesh

    b, s, d = x.shape
    e, k_top = cfg.num_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    router_logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k_top)  # (T, k)
    if cfg.renormalize_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # aux load-balance loss (Switch): E * sum_e f_e * P_e
    pe = jnp.mean(probs, axis=0)
    fe = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(fe * pe)

    mesh = active_mesh()
    tok_axes = _token_partition(mesh, t, active_act_rules()) if mesh else None
    shards = cfg.expert_shards or e
    rep = shards // e

    if tok_axes is None:
        # single-device / tiny-batch path: local == global
        if dropless:
            capacity = t * k_top
        else:
            capacity = max(int(t * k_top * cfg.capacity_factor) // e, 1)
        capacity = -(-capacity // rep) * rep
        expert_in, dest = _dispatch_local(xt, expert_idx, e, k_top, capacity, shards)
        expert_out = _expert_ffn(p, expert_in, cfg)
        out = _combine_local(expert_out, dest, gate_vals, k_top)
    else:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        nshards = 1
        for a in tok_axes:
            nshards *= sizes[a]
        t_loc = t // nshards
        if dropless:
            cap_loc = t_loc * k_top
        else:
            cap_loc = max(int(t_loc * k_top * cfg.capacity_factor) // e, 1)
        cap_loc = -(-cap_loc // rep) * rep  # physical split must divide
        disp = jax.shard_map(
            lambda xl, il: _dispatch_local(xl, il, e, k_top, cap_loc, shards),
            mesh=mesh,
            in_specs=(P(tok_axes, None), P(tok_axes, None)),
            out_specs=(P(None, tok_axes, None), P(tok_axes)),
        )
        expert_in, dest = disp(xt, expert_idx)

        # EP all-to-all: capacity-sharded -> expert-sharded (+ cap on DP axes)
        expert_in = shard_hint(expert_in, "experts", "expert_cap", "embed")
        expert_out = _expert_ffn(p, expert_in, cfg)
        # reverse all-to-all back to capacity-sharded for the local combine
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(None, tok_axes, None))
        )
        comb = jax.shard_map(
            lambda eo, de, gv: _combine_local(eo, de, gv, k_top),
            mesh=mesh,
            in_specs=(P(None, tok_axes, None), P(tok_axes), P(tok_axes, None)),
            out_specs=P(tok_axes, None),
        )
        out = comb(expert_out, dest, gate_vals)

    if cfg.num_shared_experts:
        out = out + mlp_apply(p["shared"], xt, cfg)
    return out.reshape(b, s, d), aux


def _expert_ffn(p, expert_in, cfg):
    """Batched SwiGLU over PHYSICAL expert buffers (shards, C_phys, D).

    When cfg.expert_shards > num_experts, each expert's weights are broadcast
    over rep = shards/E physical shards (the dispatch already split its
    capacity rows between them) — EP then fills the whole 'model' axis even
    when E is smaller than it (mixtral: 8 experts on a 16-wide axis).
    Gradients of the broadcast weights sum over replicas (broadcast
    transpose), so training semantics are exactly those of E logical experts.
    """
    act = ACTIVATIONS[cfg.activation]
    dt = expert_in.dtype
    e = cfg.num_experts
    shards = cfg.expert_shards or e
    rep = shards // e

    def phys(w, axes):
        w = w.astype(dt)
        if rep > 1:
            w = jnp.broadcast_to(w[:, None], (e, rep) + w.shape[1:]).reshape(
                (shards,) + w.shape[1:]
            )
        return shard_hint(w, *axes)

    up_axes = ("experts", "expert_embed", "expert_mlp")  # (E, D, F)
    down_axes = ("experts", "expert_mlp", "expert_embed")  # (E, F, D)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, phys(p["w_gate"], up_axes)))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, phys(p["w_up"], up_axes))
    h = shard_hint(h, "experts", "expert_cap", "mlp")
    out = jnp.einsum("ecf,efd->ecd", h, phys(p["w_down"], down_axes))
    # pin the output layout: without this SPMD may satisfy the (c from h,
    # d from w) sharding conflict by all-gathering h — measured 140 GiB/dev
    return shard_hint(out, "experts", "expert_cap", "embed")
