"""Workload expansion: ArchConfig x serving regime -> per-block GEMM sets.

Layer 1 of the serving subsystem (DESIGN.md §Serving-workloads).  A model
config from ``repro.configs.registry`` is walked block by block — attention,
latent attention, Mamba, m/sLSTM, dense/MoE MLP, LM head — into the concrete
GEMM shapes one forward step executes under a serving regime:

  * ``prefill``: M = batch * seq_len tokens flow through every projection;
  * ``decode``:  M = the decode-step token count, derived from the SAME
    ``configs.registry.token_shape`` helper the LM stack's batch specs use
    (seq axis == 1), so the serving expansion and ``decode_batch_specs``
    can never drift apart.

Leading dense layers (``cfg.first_k_dense``) are emitted once, with their
count, before the periodic walk of ``stage_pattern``; each runs the
pattern's first mixer and a dense MLP of width ``d_ff``.

Multi-head latent attention (``mla``, DeepSeek-V2 §2.1) is the one mixer
whose GEMMs depend on the regime.  Both regimes share the down projections
``mla.q_a`` (d -> q latent), ``mla.kv_a`` (d -> kv latent + rope dims) and
the up projection ``mla.q_b`` (q latent -> heads x (nope + rope)) and
``mla.o``.  Prefill runs the expanded form: ``mla.kv_b`` lifts the kv
latent to per-head keys and values (kv latent -> heads x (nope + v)).
Decode runs the absorbed form over the latent cache: no ``kv_b`` over the
cache, but two per-head stationary products, ``mla.uk`` (q_nope against
W_UK, nope -> kv latent) and ``mla.uv`` (latent context against W_UV, kv
latent -> v), each repeated once per head.

Routed experts follow the exact even split of the step's ``t * top_k``
expert rows over the E experts: min(E, t*top_k) experts are active; when
t*top_k <= E each active expert gets one row, otherwise r = t*top_k mod E
experts get q + 1 rows and E - r get q, with q = t*top_k // E.  So the
priced routed rows equal t*top_k exactly, and the router and any shared
experts run at the full token batch.  Attention score/context products
(QK^T, PV, and their latent forms) are cache-shaped dynamic-by-dynamic
products served by the flash-attention kernel, not stationary-weight GEMMs,
and are out of scope here.

Every emitted ``ServingGemm`` carries a ``count`` multiplicity (layers x
heads x experts ...) so identical shapes collapse to one entry, and an
``input_density`` hint for post-activation operand streams (down
projections see ~half-zero SiLU/GELU outputs).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Sequence

from repro.configs.registry import token_shape
from repro.core.workloads import Gemm

__all__ = [
    "ServingGemm",
    "REGIMES",
    "expand_arch",
    "expand_shape",
    "regime_tokens",
    "routing_sparsity",
    "validate_job_set",
]

REGIMES = ("prefill", "decode")

# density hint for operands that just passed a SiLU/GELU-style gate:
# roughly half the activations are (near-)zero, matching the synthetic
# post-activation streams ``core.workloads.gemm_job`` generates.
_POST_ACT_DENSITY = 0.5


@dataclasses.dataclass(frozen=True)
class ServingGemm:
    """One GEMM shape class a serving step executes ``count`` times.

    ``gemm.m`` is the token batch of the regime (or the per-expert
    effective batch for routed experts); K/N are the weight dims.
    """

    gemm: Gemm
    block: str  # "attn.q_proj", "moe.expert_up", "head.lm_head", ...
    regime: str  # "prefill" | "decode"
    count: int  # executions per model forward (layers x heads x experts)
    input_density: float | None = None  # post-activation stream density hint

    def __post_init__(self):
        if self.regime not in REGIMES:
            raise ValueError(f"regime must be one of {REGIMES}, got {self.regime!r}")
        if self.count < 1:
            raise ValueError(f"{self.block}: count must be >= 1, got {self.count}")
        if min(self.gemm.m, self.gemm.k, self.gemm.n) < 1:
            raise ValueError(
                f"{self.block}: non-positive GEMM dims "
                f"({self.gemm.m}, {self.gemm.k}, {self.gemm.n})"
            )

    @property
    def macs(self) -> int:
        """Total MACs this entry contributes to one forward step."""
        return self.count * self.gemm.macs


def regime_tokens(cfg, regime: str, batch: int, seq_len: int = 1) -> int:
    """Token batch M of one serving step, via the shared token-shape helper.

    Decode is DEFINED as ``token_shape(cfg, batch, 1)`` — the exact shape
    ``launch.specs.decode_batch_specs`` builds — so M is the product of its
    (batch, seq) leading axes (codebook streams share one position: the
    backbone hidden state is (B, S, d) with codebook embeddings summed).
    """
    if regime not in REGIMES:
        raise ValueError(f"regime must be one of {REGIMES}, got {regime!r}")
    if regime == "decode":
        seq_len = 1
    if batch < 1 or seq_len < 1:
        raise ValueError(f"need batch, seq_len >= 1; got {batch}, {seq_len}")
    shape = token_shape(cfg, batch, seq_len)
    return shape[0] * shape[1]


def routing_sparsity(cfg) -> float:
    """Expert-routing sparsity: active fraction of expert capacity, in (0, 1].

    ``top_k / num_experts`` for MoE configs (mixtral 2/8 = 0.25, llama4
    1/128), 1.0 for dense models (every FFN row is active).
    """
    if cfg.num_experts > 1:
        return cfg.top_k / cfg.num_experts
    return 1.0


# ---------------------------------------------------------------------------
# Per-block expansions (t = token batch of the step)
# ---------------------------------------------------------------------------


def _attn_gemms(cfg, t: int, regime: str) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    q_out = cfg.num_heads * cfg.head_dim
    kv_out = cfg.num_kv_heads * cfg.head_dim
    return [
        ("attn.q_proj", Gemm("q_proj", t, d, q_out), 1, None),
        ("attn.k_proj", Gemm("k_proj", t, d, kv_out), 1, None),
        ("attn.v_proj", Gemm("v_proj", t, d, kv_out), 1, None),
        ("attn.o_proj", Gemm("o_proj", t, q_out, d), 1, None),
    ]


def _mla_gemms(cfg, t: int, regime: str) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    h = cfg.num_heads
    nope, rope, dv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_lat, kv_lat = cfg.q_lora_rank, cfg.kv_lora_rank
    out = [
        ("mla.q_a", Gemm("q_a", t, d, q_lat), 1, None),
        ("mla.q_b", Gemm("q_b", t, q_lat, h * (nope + rope)), 1, None),
        ("mla.kv_a", Gemm("kv_a", t, d, kv_lat + rope), 1, None),
    ]
    if regime == "prefill":
        out.append(("mla.kv_b", Gemm("kv_b", t, kv_lat, h * (nope + dv)), 1, None))
    else:  # absorbed: W_UK on q_nope, W_UV on the latent context, per head
        out.append(("mla.uk", Gemm("uk", t, nope, kv_lat), h, None))
        out.append(("mla.uv", Gemm("uv", t, kv_lat, dv), h, None))
    out.append(("mla.o", Gemm("o", t, h * dv, d), 1, None))
    return out


def _mamba_gemms(cfg, t: int, regime: str) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    di = cfg.mamba_expand * d
    n = cfg.mamba_d_state
    dtr = cfg.dt_rank
    # the depthwise conv is not a GEMM; x_proj/dt_proj consume post-SiLU
    # conv output (half-zero streams)
    return [
        ("mamba.in_proj", Gemm("in_proj", t, d, 2 * di), 1, None),
        ("mamba.x_proj", Gemm("x_proj", t, di, dtr + 2 * n), 1, _POST_ACT_DENSITY),
        ("mamba.dt_proj", Gemm("dt_proj", t, dtr, di), 1, None),
        ("mamba.out_proj", Gemm("out_proj", t, di, d), 1, _POST_ACT_DENSITY),
    ]


def _mlstm_gemms(cfg, t: int, regime: str) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    di = int(cfg.xlstm_proj_factor * d)
    h = cfg.num_heads
    dh = di // h
    return [
        ("mlstm.w_up", Gemm("w_up", t, d, 2 * di), 1, None),
        # block-diagonal per-head q/k/v: h independent (t, dh) @ (dh, dh)
        ("mlstm.wqkv", Gemm("wqkv", t, dh, dh), 3 * h, None),
        ("mlstm.gates", Gemm("gates", t, di, h), 2, None),
        ("mlstm.w_down", Gemm("w_down", t, di, d), 1, _POST_ACT_DENSITY),
    ]


def _slstm_gemms(cfg, t: int, regime: str) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    h = cfg.num_heads
    dh = d // h
    ff = cfg.slstm_ff  # post-recurrence gated MLP
    return [
        # four gate input projections z/i/f/o, each (t, d) @ (d, d)
        ("slstm.w_gates", Gemm("w_gates", t, d, d), 4, None),
        # per-head block-diagonal recurrent matrices, every token, every gate
        ("slstm.r_gates", Gemm("r_gates", t, dh, dh), 4 * h, None),
        ("slstm.ff_gate", Gemm("ff_gate", t, d, ff), 1, None),
        ("slstm.ff_down", Gemm("ff_down", t, ff, d), 1, _POST_ACT_DENSITY),
    ]


def _dense_mlp_gemms(
    cfg, t: int, d_ff: int, prefix: str = "mlp"
) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    out = [(f"{prefix}.w_gate", Gemm("w_gate", t, d, d_ff), 1, None)]
    if cfg.gated_mlp:
        out.append((f"{prefix}.w_up", Gemm("w_up", t, d, d_ff), 1, None))
    out.append((f"{prefix}.w_down", Gemm("w_down", t, d_ff, d), 1, _POST_ACT_DENSITY))
    return out


def expert_rows(t: int, top_k: int, num_experts: int) -> list[tuple[int, int]]:
    """The exact even split of ``t * top_k`` routed rows over the experts:
    ``[(rows, experts), ...]``, larger share first, no zero-row entry."""
    q, r = divmod(t * top_k, num_experts)
    if q == 0:
        return [(1, r)]
    return [(m, n) for m, n in ((q + 1, r), (q, num_experts - r)) if n]


def _moe_gemms(cfg, t: int) -> list[tuple[str, Gemm, int, float | None]]:
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    out = [("moe.router", Gemm("router", t, d, cfg.num_experts), 1, None)]
    for m_e, n_e in expert_rows(t, cfg.top_k, cfg.num_experts):
        out.append(("moe.expert_gate", Gemm("expert_gate", m_e, d, ff), n_e, None))
        if cfg.gated_mlp:
            out.append(("moe.expert_up", Gemm("expert_up", m_e, d, ff), n_e, None))
        out.append(
            ("moe.expert_down", Gemm("expert_down", m_e, ff, d), n_e, _POST_ACT_DENSITY)
        )
    if cfg.num_shared_experts:
        out += _dense_mlp_gemms(
            cfg, t, ff * cfg.num_shared_experts, prefix="moe.shared"
        )
    return out


# mixer kind -> (cfg, t, regime) -> [(block, gemm, count, density)]
_MIXERS = {
    "attn": _attn_gemms,
    "mla": _mla_gemms,
    "mamba": _mamba_gemms,
    "mlstm": _mlstm_gemms,
    "slstm": _slstm_gemms,
}


def expand_arch(
    cfg, regime: str, batch: int, seq_len: int = 1
) -> list[ServingGemm]:
    """Expand one serving step of ``cfg`` into its per-block GEMM job set.

    Emits the leading dense layers once (``cfg.first_k_dense`` of them),
    then walks the stage pattern once per distinct (mixer, mlp) pair and
    scales counts by how often the pair occurs across the periodic stack
    (jamba's 7:1 mamba:attn ratio collapses to two mixer entries with
    counts 28 and 4), then appends the LM head (one per codebook —
    musicgen's 4 parallel heads).  Entries of one (block, shape) merge into
    one with the summed count; order is the deterministic walk order.
    """
    t = regime_tokens(cfg, regime, batch, seq_len)
    merged: dict[tuple, int] = {}

    def emit(entries, repeat: int):
        for block, gemm, count, density in entries:
            key = (block, gemm, density)
            merged[key] = merged.get(key, 0) + count * repeat

    def layer(mixer: str, mlp: str, repeat: int):
        if mixer not in _MIXERS:
            raise ValueError(f"{cfg.name}: unknown mixer kind {mixer!r}")
        emit(_MIXERS[mixer](cfg, t, regime), repeat)
        if mlp == "moe":
            emit(_moe_gemms(cfg, t), repeat)
        elif mlp == "dense":
            if cfg.d_ff <= 0:
                raise ValueError(f"{cfg.name}: dense MLP with d_ff={cfg.d_ff}")
            emit(_dense_mlp_gemms(cfg, t, cfg.d_ff), repeat)
        elif mlp != "none":
            raise ValueError(f"{cfg.name}: unknown mlp kind {mlp!r}")

    if cfg.first_k_dense:
        layer(cfg.stage_pattern[0][0], "dense", cfg.first_k_dense)
    pair_counts = Counter(cfg.stage_pattern)
    for pair in pair_counts:  # first-occurrence order
        layer(*pair, pair_counts[pair] * cfg.n_stages)
    emit(
        [("head.lm_head", Gemm("lm_head", t, cfg.d_model, cfg.vocab_size), 1, None)],
        cfg.num_codebooks,
    )
    return validate_job_set(
        ServingGemm(gemm=g, block=b, regime=regime, count=n, input_density=dens)
        for (b, g, dens), n in merged.items()
    )


def expand_shape(cfg, shape) -> list[ServingGemm]:
    """Expand a registry ``ShapeSpec`` cell (prefill_32k, decode_32k, ...).

    Decode cells use only the global batch (seq_len parameterizes the KV
    cache, not the per-step GEMMs); train cells expand like prefill (the
    forward GEMM set — backward doubles it but adds no new shapes).
    """
    regime = "decode" if shape.kind == "decode" else "prefill"
    if regime == "decode":
        return expand_arch(cfg, "decode", shape.global_batch)
    return expand_arch(cfg, "prefill", shape.global_batch, shape.seq_len)


def validate_job_set(jobs: Sequence[ServingGemm]) -> list[ServingGemm]:
    """Contract check: non-empty, positive shapes/counts, known regimes."""
    jobs = list(jobs)
    if not jobs:
        raise ValueError("empty GEMM job set")
    for j in jobs:
        # ServingGemm.__post_init__ already validated; re-assert the
        # aggregate invariant cheaply for externally assembled sets
        if j.macs <= 0:
            raise ValueError(f"{j.block}: non-positive MACs")
    return jobs
