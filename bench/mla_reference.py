#!/usr/bin/env python3
"""Plain reference of DeepSeek-V3's layers, and the census of their GEMMs.

A straightforward ``jax.numpy`` float32 forward of the published equations
(DeepSeek-V3, arXiv:2412.19437; MLA as defined in DeepSeek-V2,
arXiv:2405.04434 §2.1), run under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program under test.

- RMSNorm before each sub-layer and on both latents; rotary embedding on the
  64-dim part of each query head and on the one rope key shared by all heads.
- MLA in two forms. ``mla_expanded`` runs a whole causal sequence as prefill
  does: ``kv_b`` lifts the kv latent to per-head keys and values.
  ``mla_absorbed`` runs one token per sequence as decode does, against a
  latent cache of (kv latent + rope) numbers per position: W_UK is applied to
  the query's no-rope part and W_UV to the latent context, per head, and no
  key or value is ever formed.
- A dense SwiGLU FFN (the leading layers) and the MoE: sigmoid router scores,
  top-8, weights normalized over the chosen experts and scaled by
  ``routed_scaling_factor``, plus the shared expert. The MoE can hold a
  subset of the experts, as one chip of an expert-parallel deployment does:
  it routes over all of them and adds only what the held ones give.

Departures from the published model, none of which changes a GEMM shape:
YaRN rope scaling and its softmax-scale correction are left out (plain rope
at theta 10000, scale 1/sqrt(nope + rope)); rope is applied in the
rotate-half layout, not HF's interleaved one; routing is plain top-8 over
all experts with no expert groups (``n_group``/``topk_group``) and no
load-balancing bias; the MTP module is not modelled. Embeddings are random
vectors, not a vocabulary lookup.

``census`` reads the reference's jaxpr: every ``dot_general`` of a weight
with an activation, as (block, m, k, n) with its count; a product of two
activations (attention scores and context) is left out, as the program's
expander leaves it out. Blocks are the named scopes of ``wdot``.

``closed_form`` gives the published parameter and MAC counts.

On the chip (``python3 bench/mla_reference.py --out chiprun_out/mla.json``):
one MLA layer and one MoE layer at published widths holding 8 of the 256
experts; a prefill of 2048 tokens fills the latent cache, then 32 decode
steps run absorbed, and each step's output is compared with the expanded
forward of the whole sequence, computed in head blocks. The same decode in
bfloat16 is the control, which must fail the tolerance.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import time
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

# Relative error (max |decode - expanded| / max |expanded|) allowed between
# absorbed decode and the expanded forward in float32. The two forms
# associate the same products differently (W_UK q before the dot with the
# latent, against k = W_UK c): float32 rounding over K of 512-18432 and a
# softmax over ~2k positions gives errors of order 1e-6; bfloat16 (8 bits of
# mantissa) gives order 1e-2. 1e-4 sits between, with room both ways.
TOLERANCE = 1e-4


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    d_ff: int
    moe_ff: int
    n_experts: int
    top_k: int
    n_shared: int
    vocab: int
    n_layers: int
    first_k_dense: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    routed_scale: float = 2.5


def from_hf(doc: dict) -> Dims:
    """Dims from the keys of a DeepSeek-V3 ``config.json``."""
    return Dims(
        d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        q_lora=doc["q_lora_rank"],
        kv_lora=doc["kv_lora_rank"],
        nope=doc["qk_nope_head_dim"],
        rope=doc["qk_rope_head_dim"],
        v=doc["v_head_dim"],
        d_ff=doc["intermediate_size"],
        moe_ff=doc["moe_intermediate_size"],
        n_experts=doc["n_routed_experts"],
        top_k=doc["num_experts_per_tok"],
        n_shared=doc["n_shared_experts"],
        vocab=doc["vocab_size"],
        n_layers=doc["num_hidden_layers"],
        first_k_dense=doc["first_k_dense_replace"],
        rope_theta=float(doc["rope_theta"]),
        eps=doc["rms_norm_eps"],
        routed_scale=doc["routed_scaling_factor"],
    )


PUBLISHED = Dims(7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 256, 8, 1, 129280, 61, 3)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _w(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5


def _norm_w(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def init_mla(key, dm: Dims) -> dict:
    k = jax.random.split(key, 7)
    h = dm.n_heads
    return {
        "wq_a": _w(k[0], (dm.d_model, dm.q_lora), dm.d_model),
        "q_norm": _norm_w(k[1], dm.q_lora),
        "wq_b": _w(k[2], (dm.q_lora, h, dm.nope + dm.rope), dm.q_lora),
        "wkv_a": _w(k[3], (dm.d_model, dm.kv_lora + dm.rope), dm.d_model),
        "kv_norm": _norm_w(k[4], dm.kv_lora),
        "wkv_b": _w(k[5], (dm.kv_lora, h, dm.nope + dm.v), dm.kv_lora),
        "wo": _w(k[6], (h, dm.v, dm.d_model), h * dm.v),
    }


def init_ffn(key, d: int, ff: int) -> dict:
    k = jax.random.split(key, 3)
    return {"w_gate": _w(k[0], (d, ff), d), "w_up": _w(k[1], (d, ff), d), "w_down": _w(k[2], (ff, d), ff)}


def init_moe(key, dm: Dims, held) -> dict:
    """Router over all experts; the weights of the ``held`` experts only."""
    k = jax.random.split(key, 2 + dm.n_experts)
    experts = [init_ffn(k[2 + e], dm.d_model, dm.moe_ff) for e in held]
    return {
        "router": _w(k[0], (dm.d_model, dm.n_experts), dm.d_model),
        "held": np.asarray(held, np.int64),
        "experts": {n: jnp.stack([x[n] for x in experts]) for n in ("w_gate", "w_up", "w_down")},
        "shared": init_ffn(k[1], dm.d_model, dm.moe_ff * dm.n_shared),
    }


def init_layer(key, dm: Dims, kind: str, held=None) -> dict:
    """One decoder layer: ``kind`` "dense" (leading layers) or "moe"."""
    k = jax.random.split(key, 4)
    p = {"attn_norm": _norm_w(k[0], dm.d_model), "mla": init_mla(k[1], dm),
         "ffn_norm": _norm_w(k[2], dm.d_model)}
    if kind == "dense":
        p["ffn"] = init_ffn(k[3], dm.d_model, dm.d_ff)
    else:
        p["moe"] = init_moe(k[3], dm, range(dm.n_experts) if held is None else held)
    return p


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def wdot(block: str, spec: str, x, w):
    """A weight x activation product, named for the census."""
    with jax.named_scope(block):
        return jnp.einsum(spec, x, w.astype(x.dtype))


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x`` (..., S, [H,] r) at ``pos`` (..., S)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos[..., None].astype(jnp.float32) * inv  # (..., S, r/2)
    if x.ndim == pos.ndim + 2:  # a heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _queries(p, dm: Dims, x, pos):
    c_q = rms_norm(wdot("mla.q_a", "...d,dq->...q", x, p["wq_a"]), p["q_norm"], dm.eps)
    q = wdot("mla.q_b", "...q,qhe->...he", c_q, p["wq_b"])
    return q[..., : dm.nope], rope(q[..., dm.nope :], pos, dm.rope_theta)


def latent(p, dm: Dims, x, pos):
    """What the cache holds per position: the normed kv latent and the
    rope key, (..., S, kv_lora) and (..., S, rope)."""
    kv = wdot("mla.kv_a", "...d,dc->...c", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., : dm.kv_lora], p["kv_norm"], dm.eps)
    return c_kv, rope(kv[..., dm.kv_lora :], pos, dm.rope_theta)


def _scale(dm: Dims):
    return 1.0 / math.sqrt(dm.nope + dm.rope)


def mla_expanded(p, dm: Dims, x, pos, head_block: int | None = None):
    """Causal MLA over a whole sequence x (S, d) at positions pos (S,), in
    the expanded form; attention is computed ``head_block`` heads at a time."""
    s = x.shape[0]
    q_nope, q_pe = _queries(p, dm, x, pos)
    c_kv, k_pe = latent(p, dm, x, pos)
    kv = wdot("mla.kv_b", "sc,che->she", c_kv, p["wkv_b"])
    k_nope, v = kv[..., : dm.nope], kv[..., dm.nope :]
    mask = jnp.tril(jnp.ones((s, s), bool))
    hb = head_block or dm.n_heads
    outs = []
    for h0 in range(0, dm.n_heads, hb):
        hs = slice(h0, h0 + hb)
        sc = jnp.einsum("shd,thd->hst", q_nope[:, hs], k_nope[:, hs])
        sc = sc + jnp.einsum("shr,tr->hst", q_pe[:, hs], k_pe)
        sc = jnp.where(mask, sc * _scale(dm), -jnp.inf)
        outs.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, axis=-1), v[:, hs]))
    o = jnp.concatenate(outs, axis=1)
    return wdot("mla.o", "shv,hvd->sd", o, p["wo"])


def mla_absorbed(p, dm: Dims, x, pos, cache):
    """One token per sequence, x (B, d) at pos (B,), through the latent
    cache (c_kv (B, L, kv_lora), k_pe (B, L, rope)): writes the token's
    latent at ``pos`` and attends over positions <= pos. Returns (out, cache)."""
    b = x.shape[0]
    q_nope, q_pe = _queries(p, dm, x, pos)
    c_new, k_new = latent(p, dm, x, pos)
    c_kv, k_pe = cache
    rows = jnp.arange(b)
    c_kv = c_kv.at[rows, pos].set(c_new.astype(c_kv.dtype))
    k_pe = k_pe.at[rows, pos].set(k_new.astype(k_pe.dtype))
    w_uk = p["wkv_b"][:, :, : dm.nope]  # (kv_lora, H, nope)
    w_uv = p["wkv_b"][:, :, dm.nope :]  # (kv_lora, H, v)
    q_lat = wdot("mla.uk", "bhd,chd->bhc", q_nope, w_uk)
    sc = jnp.einsum("bhc,blc->bhl", q_lat, c_kv) + jnp.einsum("bhr,blr->bhl", q_pe, k_pe)
    live = jnp.arange(c_kv.shape[1])[None, None, :] <= pos[:, None, None]
    sc = jnp.where(live, sc * _scale(dm), -jnp.inf)
    ctx = jnp.einsum("bhl,blc->bhc", jax.nn.softmax(sc, axis=-1), c_kv)
    o = wdot("mla.uv", "bhc,chd->bhd", ctx, w_uv)
    return wdot("mla.o", "bhv,hvd->bd", o, p["wo"]), (c_kv, k_pe)


DENSE = ("mlp.w_gate", "mlp.w_up", "mlp.w_down")
SHARED = ("moe.shared.w_gate", "moe.shared.w_up", "moe.shared.w_down")
EXPERT = ("moe.expert_gate", "moe.expert_up", "moe.expert_down")


def ffn(p, x, blocks):
    """SwiGLU FFN; ``blocks`` names its gate, up and down products."""
    g = wdot(blocks[0], "td,df->tf", x, p["w_gate"])
    u = wdot(blocks[1], "td,df->tf", x, p["w_up"])
    return wdot(blocks[2], "tf,fd->td", jax.nn.silu(g) * u, p["w_down"])


def topk_route(scores, top_k: int) -> np.ndarray:
    """Plain top-k expert choice per token, from concrete scores."""
    return np.asarray(jax.lax.top_k(scores, top_k)[1])


def even_route(t: int, top_k: int, n_experts: int) -> np.ndarray:
    """Token i takes experts (i*k + j) mod E: t*k rows spread over the
    experts as evenly as they go, the routing the program prices."""
    return (np.arange(t * top_k) % n_experts).reshape(t, top_k)


def router_scores(p, x):
    return jax.nn.sigmoid(wdot("moe.router", "td,de->te", x, p["router"]))


def moe(p, dm: Dims, x, route):
    """MoE of tokens x (t, d); ``route(scores)`` gives the concrete
    (t, top_k) expert choice. Adds what the held experts give, and the
    shared expert."""
    scores = router_scores(p, x)
    choice = route(scores)
    gates = jnp.take_along_axis(scores, jnp.asarray(choice), axis=1)
    gates = gates / jnp.sum(gates, axis=1, keepdims=True) * dm.routed_scale
    out = ffn(p["shared"], x, SHARED)
    for j, e in enumerate(p["held"]):
        tok, slot = np.nonzero(choice == e)
        if tok.size == 0:
            continue
        w = {n: p["experts"][n][j] for n in ("w_gate", "w_up", "w_down")}
        y = ffn(w, x[tok], EXPERT)
        out = out.at[tok].add(y * gates[tok, slot][:, None])
    return out


def layer(p, dm: Dims, x, pos, *, route, cache=None, head_block=None):
    """One decoder layer. Without ``cache``: x (S, d) is one sequence,
    expanded. With it: x (B, d) is one token per sequence, absorbed.
    ``route(scores)`` gives the concrete (t, top_k) expert choice.
    Returns (y, mla_out, cache)."""
    h = rms_norm(x, p["attn_norm"], dm.eps)
    if cache is None:
        a = mla_expanded(p["mla"], dm, h, pos, head_block)
    else:
        a, cache = mla_absorbed(p["mla"], dm, h, pos, cache)
    x = x + a
    h = rms_norm(x, p["ffn_norm"], dm.eps)
    if "ffn" in p:
        y = ffn(p["ffn"], h, DENSE)
    else:
        y = moe(p["moe"], dm, h, route)
    return x + y, a, cache


def head(w, x):
    return wdot("head.lm_head", "td,dv->tv", x, w)


# ---------------------------------------------------------------------------
# Census of the jaxpr
# ---------------------------------------------------------------------------


def _dot_classes(jaxpr, weight_vars: set, prefix: str, out: Counter):
    """Walk ``jaxpr``: a var derived from weights alone is in ``weight_vars``;
    each dot_general of such a var with an activation is counted."""

    def is_w(v):
        return not isinstance(v, Literal) and v in weight_vars

    for eqn in jaxpr.eqns:
        stack = "/".join(x for x in (prefix, str(eqn.source_info.name_stack)) if x)
        if eqn.primitive.name == "dot_general":
            lhs, rhs = eqn.invars
            lw, rw = is_w(lhs), is_w(rhs)
            if lw != rw:
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                a, w = (rhs, lhs) if lw else (lhs, rhs)
                ac, wc, ab, wb = (rc, lc, rb, lb) if lw else (lc, rc, lb, rb)
                k = math.prod(w.aval.shape[i] for i in wc)
                count = math.prod(w.aval.shape[i] for i in wb)
                m = math.prod(s for i, s in enumerate(a.aval.shape) if i not in ac and i not in ab)
                n = math.prod(s for i, s in enumerate(w.aval.shape) if i not in wc and i not in wb)
                out[(stack.split("/")[0], m, k, n)] += count
        for sub in eqn.params.values():
            sub = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            if isinstance(sub, Jaxpr):
                inner = {iv for iv, v in zip(sub.invars, eqn.invars) if is_w(v)}
                _dot_classes(sub, inner, stack, out)
        if all(is_w(v) for v in eqn.invars if not isinstance(v, Literal)):
            weight_vars.update(eqn.outvars)


def census(fn, weights, *acts) -> Counter:
    """{(block, m, k, n): count} of ``fn(weights, *acts)``; ``weights`` is
    a pytree whose leaves may be ``jax.ShapeDtypeStruct``."""
    closed = jax.make_jaxpr(fn)(weights, *acts)
    n_w = len(jax.tree.leaves(weights))
    out: Counter = Counter()
    _dot_classes(closed.jaxpr, set(closed.jaxpr.invars[:n_w]), "", out)
    return out


@functools.lru_cache(maxsize=None)
def _layer_shapes(dm: Dims, kind: str):
    """A layer's weights as ``jax.ShapeDtypeStruct``, all experts held
    (their ids left out: the census puts them back)."""
    shapes = jax.eval_shape(lambda: init_layer(jax.random.PRNGKey(0), dm, kind))
    if kind == "moe":
        shapes["moe"] = {k: v for k, v in shapes["moe"].items() if k != "held"}
    return shapes


def step_census(dm: Dims, regime: str, t: int) -> Counter:
    """The GEMM census of one serving step of the whole model, t tokens:
    prefill as one sequence of t, decode as t sequences of one token over a
    cache of 16 positions (the cache length changes no weight product).
    Traced abstractly (no weight is allocated); routing is ``even_route``."""
    route = lambda scores: even_route(t, dm.top_k, dm.n_experts)  # noqa: E731
    lead, rest = dm.first_k_dense, dm.n_layers - dm.first_k_dense
    sds = jax.ShapeDtypeStruct
    acts = (sds((t, dm.d_model), jnp.float32), sds((t,), jnp.int32))
    cache = None
    if regime == "decode":
        cache = (sds((t, 16, dm.kv_lora), jnp.float32), sds((t, 16, dm.rope), jnp.float32))
    total: Counter = Counter()
    for kind, times in (("dense", lead), ("moe", rest)):
        if not times:
            continue
        shapes = _layer_shapes(dm, kind)

        def fn(w, x, pos, *c, kind=kind):
            if kind == "moe":
                w = {**w, "moe": {**w["moe"], "held": np.arange(dm.n_experts)}}
            return layer(w, dm, x, pos, route=route, cache=tuple(c) or None)[0]

        one = census(fn, shapes, *acts, *(cache or ()))
        for key, c in one.items():
            total[key] += c * times
    total.update(census(head, sds((dm.d_model, dm.vocab), jnp.float32), acts[0]))
    return total


# ---------------------------------------------------------------------------
# Closed forms
# ---------------------------------------------------------------------------


def closed_form(dm: Dims) -> dict:
    """Parameters (total and active) and decode MAC per token at batch 1
    under even routing, from the published equations. Norm weights count;
    the router's load-balancing bias and the MTP module do not."""
    d, h = dm.d_model, dm.n_heads
    mla = (d * dm.q_lora + dm.q_lora + dm.q_lora * h * (dm.nope + dm.rope)
           + d * (dm.kv_lora + dm.rope) + dm.kv_lora + dm.kv_lora * h * (dm.nope + dm.v)
           + h * dm.v * d)
    expert = 3 * d * dm.moe_ff
    dense_ffn = 3 * d * dm.d_ff
    moe_fixed = d * dm.n_experts + dm.n_shared * expert
    lead, rest = dm.first_k_dense, dm.n_layers - dm.first_k_dense
    layers = dm.n_layers * (mla + 2 * d) + lead * dense_ffn + rest * moe_fixed
    total = 2 * dm.vocab * d + d + layers + rest * dm.n_experts * expert
    active = 2 * dm.vocab * d + d + layers + rest * dm.top_k * expert
    mla_macs = (d * dm.q_lora + dm.q_lora * h * (dm.nope + dm.rope) + d * (dm.kv_lora + dm.rope)
                + h * dm.nope * dm.kv_lora + h * dm.kv_lora * dm.v + h * dm.v * d)
    decode = (dm.n_layers * mla_macs + lead * dense_ffn
              + rest * (moe_fixed + dm.top_k * expert) + d * dm.vocab)
    return {"params": total, "active_params": active, "decode_macs_per_token": decode}


# ---------------------------------------------------------------------------
# Absorbed decode against the expanded forward
# ---------------------------------------------------------------------------


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if isinstance(x, jax.Array) else x, tree)


def compare_decode(dm: Dims, seed: int, prefill: int, decode: int, held,
                   kinds=("moe",), head_block=None, dtype=jnp.float32) -> dict:
    """Layers of ``kinds`` on one sequence of prefill + decode tokens: the
    expanded forward of the whole sequence is the reference; the system
    fills the latent cache by a prefill of ``prefill`` tokens and decodes
    the rest absorbed, one token a step, in ``dtype``. Returns the largest
    relative errors over the decoded positions, of the MLA outputs and of
    the layer outputs, layer by layer."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, len(kinds) + 1)
    params = [init_layer(k, dm, kind, held) for k, kind in zip(ks[1:], kinds)]
    s = prefill + decode
    x0 = jax.random.normal(ks[0], (s, dm.d_model), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    topk = lambda scores: topk_route(scores, dm.top_k)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        # reference: the expanded forward of the whole sequence, layer by layer
        ref_y, ref_a, x = [], [], x0
        for p in params:
            x, a, _ = layer(p, dm, x, pos, route=topk, head_block=head_block)
            ref_y.append(x)
            ref_a.append(a)
        # system: prefill fills each layer's latent cache, then absorbed decode
        sp = [_cast(p, dtype) for p in params]
        caches, x = [], x0[:prefill].astype(dtype)
        for p in sp:
            c_kv, k_pe = latent(p["mla"], dm, rms_norm(x, p["attn_norm"], dm.eps), pos[:prefill])
            pad = ((0, decode), (0, 0))
            caches.append((jnp.pad(c_kv, pad)[None], jnp.pad(k_pe, pad)[None]))
            if prefill:
                x, _, _ = layer(p, dm, x, pos[:prefill], route=topk, head_block=head_block)
        err_a = [0.0] * len(kinds)
        err_y = [0.0] * len(kinds)
        for i in range(prefill, s):
            x = x0[i : i + 1].astype(dtype)
            for li, p in enumerate(sp):
                x, a, caches[li] = layer(p, dm, x, pos[i : i + 1], route=topk, cache=caches[li])
                err_a[li] = max(err_a[li], rel_err(a[0], ref_a[li][i]))
                err_y[li] = max(err_y[li], rel_err(x[0], ref_y[li][i]))
    return {"mla_rel_err": err_a, "layer_rel_err": err_y}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill", type=int, default=2048)
    ap.add_argument("--decode", type=int, default=32)
    ap.add_argument("--held", type=int, default=8, help="experts held of the 256")
    ap.add_argument("--head-block", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    dm = PUBLISHED
    rng = np.random.default_rng(args.seed)
    held = np.sort(rng.choice(dm.n_experts, size=args.held, replace=False))
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "dims": dataclasses.asdict(dm), "held": held.tolist(),
           "prefill": args.prefill, "decode": args.decode, "tolerance": TOLERANCE,
           "closed_form": closed_form(dm)}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        t0 = time.perf_counter()
        r = compare_decode(dm, args.seed, args.prefill, args.decode, held,
                           kinds=("moe",), head_block=args.head_block, dtype=dtype)
        r["seconds"] = time.perf_counter() - t0
        r["within_tolerance"] = max(r["mla_rel_err"] + r["layer_rel_err"]) <= TOLERANCE
        res[name] = r
        print(name, json.dumps(r), flush=True)
    res["correct"] = res["float32"]["within_tolerance"] and not res["bfloat16"]["within_tolerance"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    print(json.dumps({"correct": res["correct"], "closed_form": res["closed_form"]}))
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
