"""Model assembly: config-driven heterogeneous block stacks, scanned.

A model is ``n_stages`` repetitions of ``cfg.stage_pattern`` (a tuple of
(mixer, mlp) block kinds). All stage parameters are stacked along a leading
'layers' axis and the stack is executed with ``jax.lax.scan`` — HLO size is
O(stage pattern), not O(depth), which keeps 1000-node compiles (and this
container's 1-CPU dry-runs) tractable.

Public entry points:
  init_params / init_cache      -> (pytree, logical-axes pytree)
  forward(cfg, params, batch)   -> logits (full seq, or last position)
  loss_fn                       -> (loss, metrics)
  decode_step                   -> (logits, new cache)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import blocks, ssm, xlstm
from repro.models.layers import dense_param, ones_param, rms_norm
from repro.parallel.sharding import shard_hint

Params = dict
Axes = dict


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

_MIXER_INIT = {
    "attn": blocks.attn_init,
    "mamba": ssm.mamba_init,
    "mlstm": xlstm.mlstm_init,
    "slstm": xlstm.slstm_init,
}


def builds(cfg) -> bool:
    """Whether this stack builds ``cfg`` (parameters, forward, loss, cache
    and decode): every mixer but latent attention, and no leading dense
    layers. ``repro.configs.params`` counts every config's parameters."""
    return not cfg.first_k_dense and all(m != "mla" for m, _ in cfg.stage_pattern)


def _require_builds(cfg):
    if not builds(cfg):
        raise NotImplementedError(
            f"{cfg.name}: latent attention and leading dense layers are not "
            "built by this stack (repro.configs.params counts their parameters)"
        )


def init_params(cfg, key: jax.Array) -> tuple[Params, Axes]:
    _require_builds(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    k_embed, k_head, k_stages = jax.random.split(key, 3)
    p: Params = {}
    a: Axes = {}

    if cfg.num_codebooks > 1:
        p["embed"], a["embed"] = dense_param(
            k_embed,
            (cfg.num_codebooks, cfg.vocab_size, cfg.d_model),
            ("codebooks", "vocab", "embed"),
            scale=1.0,
        )
        p["head"], a["head"] = dense_param(
            k_head,
            (cfg.num_codebooks, cfg.d_model, cfg.vocab_size),
            ("codebooks", "embed", "vocab"),
        )
    else:
        p["embed"], a["embed"] = dense_param(
            k_embed, (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), scale=1.0
        )
        p["head"], a["head"] = dense_param(
            k_head, (cfg.d_model, cfg.vocab_size), ("embed", "vocab")
        )
    p["final_norm"], a["final_norm"] = ones_param((cfg.d_model,), ("embed",))

    stages_p: dict[str, Any] = {}
    stages_a: dict[str, Any] = {}
    keys = jax.random.split(k_stages, len(cfg.stage_pattern))
    for i, (mixer, mlp) in enumerate(cfg.stage_pattern):
        bk = jax.random.split(keys[i], 4)
        bp: dict[str, Any] = {}
        ba: dict[str, Any] = {}
        bp["ln1"], ba["ln1"] = ones_param((cfg.d_model,), ("embed",), stack=cfg.n_stages)
        bp["mixer"], ba["mixer"] = _MIXER_INIT[mixer](bk[0], cfg, cfg.n_stages)
        if mlp == "dense":
            bp["ln2"], ba["ln2"] = ones_param((cfg.d_model,), ("embed",), stack=cfg.n_stages)
            bp["mlp"], ba["mlp"] = blocks.mlp_init(bk[1], cfg, cfg.n_stages)
        elif mlp == "moe":
            bp["ln2"], ba["ln2"] = ones_param((cfg.d_model,), ("embed",), stack=cfg.n_stages)
            bp["mlp"], ba["mlp"] = blocks.moe_init(bk[1], cfg, cfg.n_stages)
        stages_p[f"block{i}"] = bp
        stages_a[f"block{i}"] = ba
    p["stages"] = stages_p
    a["stages"] = stages_a

    if dtype != jnp.float32:
        p = jax.tree.map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, p
        )
    return p, a


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens, dtype):
    if cfg.num_codebooks > 1:
        # tokens: (B, S, K); sum the K codebook embeddings
        parts = [
            jnp.take(params["embed"][k], tokens[..., k], axis=0)
            for k in range(cfg.num_codebooks)
        ]
        x = sum(parts)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(dtype)


def _head(cfg, params, x):
    if cfg.num_codebooks > 1:
        return jnp.einsum("...d,kdv->...kv", x, params["head"].astype(x.dtype))
    return x @ params["head"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _stage_fn(cfg, x, stage_params, positions):
    aux = jnp.zeros((), jnp.float32)
    block_remat = cfg.remat == "block"

    def mixer_block(x, bp, kind):
        # norms run on the seq-sharded residual; the SP->TP layout transition
        # (all-gather) is pinned HERE, on the bf16 post-norm tensor — without
        # this hint XLA gathers the f32 norm upcast (2x collective bytes)
        h = shard_hint(rms_norm(x, bp["ln1"]), "batch", None, "embed")
        if kind == "attn":
            y = blocks.attn_apply(bp["mixer"], h, cfg, positions)
        elif kind == "mamba":
            y = ssm.mamba_apply(bp["mixer"], h, cfg)
        elif kind == "mlstm":
            y = xlstm.mlstm_apply(bp["mixer"], h, cfg)
        else:
            y = xlstm.slstm_apply(bp["mixer"], h, cfg)
        return x + shard_hint(y, "batch", "seq", "embed")

    def mlp_block(x, bp, kind):
        h = shard_hint(rms_norm(x, bp["ln2"]), "batch", None, "embed")
        if kind == "dense":
            y = blocks.mlp_apply(bp["mlp"], h, cfg)
            a = jnp.zeros((), jnp.float32)
        else:
            y, a = blocks.moe_apply(bp["mlp"], h, cfg)
        return x + shard_hint(y, "batch", "seq", "embed"), a

    if block_remat:
        # per-block checkpoints: backward keeps ONE block's activations live
        # instead of a whole stage's (jamba: 8 blocks/stage — 4x temp cut)
        mixer_block = jax.checkpoint(mixer_block, static_argnums=(2,))
        mlp_block = jax.checkpoint(mlp_block, static_argnums=(2,))

    for i, (mixer, mlp) in enumerate(cfg.stage_pattern):
        bp = stage_params[f"block{i}"]
        x = mixer_block(x, bp, mixer)
        if mlp != "none":
            x, a = mlp_block(x, bp, mlp)
            aux = aux + a
        x = shard_hint(x, "batch", "seq", "embed")
    return x, aux


def default_positions(cfg, batch: int, seq: int) -> jnp.ndarray:
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(pos, (3, batch, seq))
    return pos


def hidden_forward(
    cfg,
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Embed + stage stack + final norm. Returns (hidden (B, S, D), aux)."""
    _require_builds(cfg)
    dtype = jnp.dtype(cfg.compute_dtype)
    b, s = tokens.shape[0], tokens.shape[1]
    if positions is None:
        positions = default_positions(cfg, b, s)
    x = _embed(cfg, params, tokens, dtype)
    x = shard_hint(x, "batch", "seq", "embed")

    def body(carry, stage_params):
        xc, aux = carry
        xn, a = _stage_fn(cfg, xc, stage_params, positions)
        return (xn, aux + a), None

    # 'stage' (alias 'full'): checkpoint whole stages; 'block': per-block
    # checkpoints inside _stage_fn, stage body saved too (outer checkpoint is
    # then redundant recompute — skip it); 'dots': stage checkpoint that SAVES
    # matmul outputs (no FSDP weight re-gathers in backward, more memory);
    # 'none': save everything.
    if cfg.remat in ("full", "stage"):
        body_fn = jax.checkpoint(body)
    elif cfg.remat == "dots":
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    else:
        body_fn = body
    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.n_stages <= 2:
        # unrolled (exact cost_analysis for the dry-run's depth extrapolation)
        for i in range(cfg.n_stages):
            sp = jax.tree.map(lambda t: t[i], params["stages"])
            carry, _ = body_fn(carry, sp)
        x, aux = carry
    else:
        (x, aux), _ = jax.lax.scan(body_fn, carry, params["stages"])
    return rms_norm(x, params["final_norm"]), aux


def forward(
    cfg,
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray | None = None,
    *,
    last_only: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss).

    ``last_only`` returns next-token logits for the final position only — the
    serving prefill path (full (B, S, V) logits at 32k x 200k vocab would be
    hundreds of GB and serve no purpose).
    """
    x, aux = hidden_forward(cfg, params, tokens, positions)
    if last_only:
        x = x[:, -1]
        x = shard_hint(x, "batch", "embed")
    else:
        x = shard_hint(x, "batch", None, "embed")  # gather seq (bf16) for head
    logits = _logits_hint(cfg, _head(cfg, params, x))
    return logits, aux


def _logits_hint(cfg, logits):
    """Keep the (huge) logits vocab-sharded: downstream reductions run over
    the sharded axis instead of all-gathering (B, S, V) per device. The seq
    axis is deliberately NOT sharded here so 'model' stays free for vocab."""
    ax = (
        ("batch",)
        + (None,) * (logits.ndim - 2 - (cfg.num_codebooks > 1))
        + (("codebooks",) if cfg.num_codebooks > 1 else ())
        + ("vocab",)
    )
    return shard_hint(logits, *ax)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def _ce_terms(cfg, head, x_chunk, labels_chunk) -> jnp.ndarray:
    """Sum over the chunk of (logsumexp - label_logit). x_chunk: (B, c, D)."""
    if cfg.num_codebooks > 1:
        logits = jnp.einsum("...d,kdv->...kv", x_chunk, head.astype(x_chunk.dtype))
    else:
        logits = x_chunk @ head.astype(x_chunk.dtype)
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    label_logit = jnp.take_along_axis(logits, labels_chunk[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - label_logit)


def loss_fn(cfg, params: Params, batch: dict) -> tuple[jnp.ndarray, dict]:
    labels = batch["labels"]
    chunk = cfg.loss_chunk
    seq = labels.shape[1]
    if chunk and seq % chunk == 0 and seq // chunk > 1:
        # chunked CE: the LM head runs per seq-chunk under remat, so the
        # (B, S, V) logits tensor never exists — per-device peak is one
        # (B, c, V) slab (recomputed in backward). Bitwise-same math.
        x, aux = hidden_forward(cfg, params, batch["tokens"], batch.get("positions"))
        nc = seq // chunk
        # hoist ONE replicated copy of the (vocab-sharded) head out of the
        # chunk scan — inside the scan body SPMD would all-gather it per
        # chunk (measured: +25% collective bytes on llama4, §Perf)
        head = shard_hint(params["head"], *(None,) * params["head"].ndim)

        def step(carry, i):
            xc = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
            lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
            return carry + _ce_terms(cfg, head, xc, lc), None

        total_nll, _ = jax.lax.scan(
            jax.checkpoint(step), jnp.zeros((), jnp.float32), jnp.arange(nc)
        )
        ce = total_nll / labels.size
    else:
        logits, aux = forward(cfg, params, batch["tokens"], batch.get("positions"))
        logits = logits.astype(jnp.float32)
        # logsumexp + gather reduce over the (possibly sharded) vocab axis
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - label_logit)
    total = ce + cfg.aux_loss_coef * aux
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# KV / state caches + decode
# ---------------------------------------------------------------------------


def cache_len_for(cfg, seq_len: int) -> int:
    return min(seq_len, cfg.window) if cfg.window else seq_len


def init_cache(cfg, batch: int, seq_len: int, dtype=None) -> tuple[Params, Axes]:
    _require_builds(cfg)
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    clen = cache_len_for(cfg, seq_len)
    cache: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    for i, (mixer, _) in enumerate(cfg.stage_pattern):
        if mixer == "attn":
            c, ax = blocks.attn_cache_init(cfg, batch, clen, cfg.n_stages, dtype)
        elif mixer == "mamba":
            c, ax = ssm.mamba_cache_init(cfg, batch, cfg.n_stages, dtype)
        elif mixer == "mlstm":
            c, ax = xlstm.mlstm_cache_init(cfg, batch, cfg.n_stages, dtype)
        else:
            c, ax = xlstm.slstm_cache_init(cfg, batch, cfg.n_stages, dtype)
        cache[f"block{i}"] = c
        axes[f"block{i}"] = ax
    return cache, axes


def decode_step(
    cfg,
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,  # (B, 1) or (B, 1, K)
    pos: jnp.ndarray,  # scalar int32: position index of this token
) -> tuple[jnp.ndarray, Params]:
    """One decoding step for the whole stack. Returns (logits (B, V[, K]), cache)."""
    _require_builds(cfg)
    dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed(cfg, params, tokens, dtype)
    x = shard_hint(x, "batch", "seq", "embed")

    def body(xc, inputs):
        stage_params, stage_cache = inputs
        new_cache = {}
        for i, (mixer, mlp) in enumerate(cfg.stage_pattern):
            bp = stage_params[f"block{i}"]
            c = stage_cache[f"block{i}"]
            h = rms_norm(xc, bp["ln1"])
            if mixer == "attn":
                y, nc = blocks.attn_decode(bp["mixer"], h, c, pos, cfg)
            elif mixer == "mamba":
                y, nc = ssm.mamba_decode(bp["mixer"], h, c, cfg)
            elif mixer == "mlstm":
                y, nc = xlstm.mlstm_decode(bp["mixer"], h, c, cfg)
            else:
                y, nc = xlstm.slstm_decode(bp["mixer"], h, c, cfg)
            new_cache[f"block{i}"] = nc
            xc = xc + y
            if mlp != "none":
                h = rms_norm(xc, bp["ln2"])
                if mlp == "dense":
                    y = blocks.mlp_apply(bp["mlp"], h, cfg)
                else:
                    # dropless at decode: a dropped token would diverge from
                    # the prefill forward pass (and T is tiny here anyway)
                    y, _ = blocks.moe_apply(bp["mlp"], h, cfg, dropless=True)
                xc = xc + y
        return xc, new_cache

    if cfg.n_stages <= 2:
        ncs = []
        for i in range(cfg.n_stages):
            sp = jax.tree.map(lambda t: t[i], params["stages"])
            sc = jax.tree.map(lambda t: t[i], cache)
            x, nc = body(x, (sp, sc))
            ncs.append(nc)
        new_cache = jax.tree.map(lambda *ts: jnp.stack(ts), *ncs)
    else:
        x, new_cache = jax.lax.scan(body, x, (params["stages"], cache))
    x = rms_norm(x, params["final_norm"])
    logits = _logits_hint(cfg, _head(cfg, params, x[:, 0]))
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill that also fills an attention KV cache (serving path)
# ---------------------------------------------------------------------------


def prefill_with_cache(cfg, params, tokens, cache_seq_len: int | None = None):
    """Run the full forward AND produce a filled decode cache.

    Simple two-pass strategy (forward for logits; per-position decode for the
    cache would be O(S) scans) is wasteful; instead we re-run the mixers'
    cache-filling math directly where cheap. For the framework's serving
    example sizes this uses the straightforward approach: sequential decode
    over positions via lax.scan of decode_step's body on each token, carrying
    the cache. Exact but sequential — fine for example/tests; production
    prefill lowers ``forward(last_only=True)`` + kernelized cache writes.
    """
    b, s = tokens.shape[0], tokens.shape[1]
    clen = cache_len_for(cfg, cache_seq_len or s)
    cache, _ = init_cache(cfg, b, clen)

    def step(carry, t):
        cache = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        logits, cache = decode_step(cfg, params, cache, tok, t)
        return cache, logits

    cache, logits_seq = jax.lax.scan(step, cache, jnp.arange(s))
    logits_last = logits_seq[-1]
    return logits_last, cache


# ---------------------------------------------------------------------------
# Parameter accounting (the reference for ``repro.configs.params``)
# ---------------------------------------------------------------------------


def shapes_and_axes(cfg) -> tuple[Any, Any]:
    """(ShapeDtypeStruct pytree, logical-axes pytree) with zero allocation.

    The axes tree contains string tuples which eval_shape cannot return, so
    it is captured through a side channel during the abstract trace.
    """
    captured = {}

    def only_params(key):
        p, a = init_params(cfg, key)
        captured["axes"] = a
        return p

    p_shapes = jax.eval_shape(only_params, jax.random.PRNGKey(0))
    return p_shapes, captured["axes"]


def count_params_analytic(
    cfg, active_only: bool = False, exclude_embed: bool = False
) -> int:
    """Exact param count of this stack's ``init_params``, via eval_shape.
    ``active_only`` scales expert tables by top_k/E (MoE active params);
    ``exclude_embed`` drops the input embedding table — the LM head IS
    counted. The closed form ``repro.configs.params.count_params`` is the
    count the rest of the repo reads; tests hold the two equal."""
    p_shapes, axes = shapes_and_axes(cfg)
    total = 0
    for leaf, ax in zip(
        jax.tree.leaves(p_shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        )),
    ):
        n = 1
        for d in leaf.shape:
            n *= d
        if exclude_embed and "vocab" in ax and "embed" in ax:
            if ax.index("vocab") < ax.index("embed"):
                continue  # input embedding table
        if active_only and "experts" in ax:
            n = n * cfg.top_k // cfg.num_experts
        total += n
    return total
