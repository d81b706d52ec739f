"""ShapeDtypeStruct stand-ins (+ logical axes) for every model input.

This is the dry-run's contract: for each (arch, shape) cell we can build the
full argument pytrees — parameters, optimizer state, batches, KV/state caches
— as zero-allocation specs, plus the parallel logical-axes trees the sharding
rules consume.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.registry import token_shape
from repro.models import model
from repro.optim import adamw

SDS = jax.ShapeDtypeStruct


def eval_shape_with_axes(fn: Callable[[], tuple[Any, Any]]) -> tuple[Any, Any]:
    """eval_shape over a () -> (arrays, axes) fn; axes via side channel
    (axes trees hold string tuples which eval_shape cannot return)."""
    captured = {}

    def arrays_only():
        arrays, axes = fn()
        captured["axes"] = axes
        return arrays

    shapes = jax.eval_shape(arrays_only)
    return shapes, captured["axes"]


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------


def _token_spec(cfg, batch: int, seq: int) -> tuple[SDS, tuple]:
    shape = token_shape(cfg, batch, seq)
    if len(shape) == 3:
        return SDS(shape, jnp.int32), ("batch", "seq", "codebooks")
    return SDS(shape, jnp.int32), ("batch", "seq")


def _position_spec(cfg, batch: int, seq: int) -> tuple[SDS, tuple]:
    if cfg.rope_kind == "mrope":
        return SDS((3, batch, seq), jnp.int32), (None, "batch", "seq")
    return SDS((batch, seq), jnp.int32), ("batch", "seq")


def train_batch_specs(cfg, shape) -> tuple[dict, dict]:
    b, s = shape.global_batch, shape.seq_len
    tok, tok_ax = _token_spec(cfg, b, s)
    pos, pos_ax = _position_spec(cfg, b, s)
    specs = {"tokens": tok, "labels": tok, "positions": pos}
    axes = {"tokens": tok_ax, "labels": tok_ax, "positions": pos_ax}
    return specs, axes


def prefill_batch_specs(cfg, shape) -> tuple[dict, dict]:
    b, s = shape.global_batch, shape.seq_len
    tok, tok_ax = _token_spec(cfg, b, s)
    pos, pos_ax = _position_spec(cfg, b, s)
    return {"tokens": tok, "positions": pos}, {"tokens": tok_ax, "positions": pos_ax}


def decode_batch_specs(cfg, shape) -> tuple[dict, dict]:
    b = shape.global_batch
    tok, tok_ax = _token_spec(cfg, b, 1)
    return (
        {"tokens": tok, "pos": SDS((), jnp.int32)},
        {"tokens": tok_ax, "pos": ()},
    )


# ---------------------------------------------------------------------------
# State / cache specs
# ---------------------------------------------------------------------------


def param_specs(cfg) -> tuple[Any, Any]:
    return model.shapes_and_axes(cfg)


def train_state_specs(cfg, opt_cfg: adamw.AdamWConfig) -> tuple[dict, dict]:
    """{'params', 'opt_state'} spec + axes trees; moments share param axes."""
    p_shapes, p_axes = param_specs(cfg)
    mdt = jnp.dtype(opt_cfg.moment_dtype)
    mom = jax.tree.map(lambda s: SDS(s.shape, mdt), p_shapes)
    state = {
        "params": p_shapes,
        "opt_state": {"m": mom, "v": mom, "step": SDS((), jnp.int32)},
    }
    axes = {
        "params": p_axes,
        "opt_state": {"m": p_axes, "v": p_axes, "step": ()},
    }
    return state, axes


def cache_specs(cfg, shape, dtype=None) -> tuple[Any, Any]:
    b, s = shape.global_batch, shape.seq_len
    return eval_shape_with_axes(lambda: model.init_cache(cfg, b, s, dtype))


def input_specs(cfg, shape) -> tuple[dict, dict]:
    """All step inputs for one (arch, shape) cell, by shape kind.

    train  -> {'state', 'batch'}
    prefill-> {'params', 'batch'}
    decode -> {'params', 'cache', 'batch'}
    """
    if shape.kind == "train":
        state, state_ax = train_state_specs(cfg, adamw.AdamWConfig())
        batch, batch_ax = train_batch_specs(cfg, shape)
        return {"state": state, "batch": batch}, {"state": state_ax, "batch": batch_ax}
    if shape.kind == "prefill":
        params, p_ax = param_specs(cfg)
        batch, batch_ax = prefill_batch_specs(cfg, shape)
        return {"params": params, "batch": batch}, {"params": p_ax, "batch": batch_ax}
    if shape.kind == "decode":
        params, p_ax = param_specs(cfg)
        cache, c_ax = cache_specs(cfg, shape)
        batch, batch_ax = decode_batch_specs(cfg, shape)
        return (
            {"params": params, "cache": cache, "batch": batch},
            {"params": p_ax, "cache": c_ax, "batch": batch_ax},
        )
    raise ValueError(f"unknown shape kind {shape.kind}")
