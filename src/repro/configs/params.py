"""Parameter counts of a registry config, in closed form.

One term per block family, each the sum of the weight shapes that block
holds (the shapes the LM stack's ``models/*_init`` functions allocate,
which ``tests/test_archs.py`` checks case by case): embeddings and LM
heads (one per codebook), the final and per-layer RMS norms, the mixers
(GQA attention with its optional biases and q/k norms, latent attention,
Mamba, mLSTM, sLSTM), dense and MoE MLPs, and the leading dense layers.
"""

from __future__ import annotations

__all__ = ["count_params"]


def _attn(cfg) -> int:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n = 2 * d * h * hd + 2 * d * kv * hd  # wq, wo; wk, wv
    if cfg.qkv_bias:
        n += (h + 2 * kv) * hd
    if cfg.qk_norm:
        n += 2 * hd
    return n


def _mla(cfg) -> int:
    d, h = cfg.d_model, cfg.num_heads
    nope, rope, dv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_lat, kv_lat = cfg.q_lora_rank, cfg.kv_lora_rank
    return (
        d * q_lat + q_lat  # q down projection, q latent norm
        + q_lat * h * (nope + rope)  # q up projection
        + d * (kv_lat + rope) + kv_lat  # kv down projection (+ rope key), norm
        + kv_lat * h * (nope + dv)  # kv up projection
        + h * dv * d  # output projection
    )


def _mamba(cfg) -> int:
    d, n, dtr = cfg.d_model, cfg.mamba_d_state, cfg.dt_rank
    di = cfg.mamba_expand * d
    return (
        d * 2 * di  # in_proj
        + (cfg.mamba_d_conv + 1) * di  # depthwise conv weight and bias
        + di * (dtr + 2 * n)  # x_proj
        + dtr * di + di  # dt_proj, dt bias
        + di * n + di  # A_log, D
        + di * d  # out_proj
    )


def _mlstm(cfg) -> int:
    d, h = cfg.d_model, cfg.num_heads
    di = int(cfg.xlstm_proj_factor * d)
    dh = di // h
    return (
        d * 2 * di  # up projection
        + 3 * h * dh * dh  # per-head block-diagonal q, k, v
        + 2 * di * h + h  # input and forget gates, forget bias
        + di  # output norm
        + di * d  # down projection
    )


def _slstm(cfg) -> int:
    d, h = cfg.d_model, cfg.num_heads
    dh = d // h
    gates = 4 * (d * d + h * dh * dh + d)  # z/i/f/o: input, recurrent, bias
    return gates + d + 2 * d * cfg.slstm_ff  # output norm, gated MLP


_MIXERS = {
    "attn": _attn,
    "mla": _mla,
    "mamba": _mamba,
    "mlstm": _mlstm,
    "slstm": _slstm,
}


def _dense_mlp(cfg, d_ff: int) -> int:
    return (3 if cfg.gated_mlp else 2) * cfg.d_model * d_ff


def _moe(cfg, active_only: bool) -> int:
    d, ff = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    experts = cfg.top_k if active_only else cfg.num_experts
    # router; gate, up and down tables of each (active) routed expert
    n = d * cfg.num_experts + 3 * experts * d * ff
    if cfg.num_shared_experts:
        n += _dense_mlp(cfg, ff * cfg.num_shared_experts)
    return n


def count_params(cfg, *, active_only: bool = False, exclude_embed: bool = False) -> int:
    """Total parameter count of ``cfg``.

    ``active_only`` counts ``top_k`` of the ``num_experts`` routed expert
    tables (MoE active parameters); ``exclude_embed`` drops the input
    embedding table (a gather, not a matmul) for 6ND model-FLOP accounting.
    The LM head is always counted.
    """
    d = cfg.d_model
    table = cfg.num_codebooks * cfg.vocab_size * d
    total = table + d  # LM head(s), final norm
    if not exclude_embed:
        total += table
    for mixer, mlp in cfg.stage_pattern:
        block = d + _MIXERS[mixer](cfg)  # pre-mixer norm
        if mlp == "dense":
            block += d + _dense_mlp(cfg, cfg.d_ff)
        elif mlp == "moe":
            block += d + _moe(cfg, active_only)
        total += cfg.n_stages * block
    if cfg.first_k_dense:  # the pattern's first mixer with a dense MLP
        lead = 2 * d + _MIXERS[cfg.stage_pattern[0][0]](cfg) + _dense_mlp(cfg, cfg.d_ff)
        total += cfg.first_k_dense * lead
    return total
