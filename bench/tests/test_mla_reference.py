"""The plain DeepSeek-V3 reference (``bench/mla_reference.py``) on the CPU, at
a small size with seeded random weights: absorbed decode through the latent
cache against the expanded forward, its bfloat16 control, the expert share,
the census rule and the closed forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import mla_reference as ref

SMALL = ref.Dims(
    d_model=64, n_heads=4, q_lora=48, kv_lora=32, nope=16, rope=8, v=24, d_ff=96,
    moe_ff=32, n_experts=8, top_k=2, n_shared=1, vocab=100, n_layers=3, first_k_dense=1,
)


@pytest.mark.parametrize("prefill", [0, 5])
def test_absorbed_decode_equals_expanded_forward(prefill):
    """A dense and an MoE layer: every decoded position, from the first
    (prefill 0) or after a prefill of 5, agrees with the expanded forward of
    the whole sequence within the tolerance; in bfloat16 it does not."""
    f32 = ref.compare_decode(SMALL, 0, prefill, 12 - prefill, range(8), kinds=("dense", "moe"))
    assert max(f32["mla_rel_err"] + f32["layer_rel_err"]) <= ref.TOLERANCE, f32
    bf16 = ref.compare_decode(
        SMALL, 0, prefill, 12 - prefill, range(8), kinds=("dense", "moe"), dtype=jnp.bfloat16
    )
    assert min(bf16["mla_rel_err"]) > 10 * ref.TOLERANCE, bf16


def test_expert_shares_add_up_to_the_layer():
    """Disjoint expert subsets, the shared expert counted once, add up to
    the uncut MoE layer."""
    key = jax.random.PRNGKey(3)
    whole = ref.init_moe(key, SMALL, range(SMALL.n_experts))
    x = jax.random.normal(jax.random.PRNGKey(4), (20, SMALL.d_model))
    route = lambda scores: ref.topk_route(scores, SMALL.top_k)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        full = ref.moe(whole, SMALL, x, route)
        shared = ref.ffn(whole["shared"], x, ref.SHARED)
        parts = []
        for held in (np.array([0, 5]), np.array([1, 2, 7]), np.array([3, 4, 6])):
            p = {**whole, "held": held,
                 "experts": {n: w[held] for n, w in whole["experts"].items()}}
            parts.append(ref.moe(p, SMALL, x, route) - shared)
    np.testing.assert_allclose(sum(parts) + shared, full, rtol=1e-5, atol=1e-5)
    # with these weights every subset gives some tokens something
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_census_counts_weight_products_only():
    """Scores and context (activation x activation) are not in the census;
    every weight product of a decode step is, once per head where per head."""
    c = ref.step_census(SMALL, "decode", 3)
    assert {b for b, *_ in c} == {
        "mla.q_a", "mla.q_b", "mla.kv_a", "mla.uk", "mla.uv", "mla.o", "mlp.w_gate",
        "mlp.w_up", "mlp.w_down", "moe.router", "moe.expert_gate", "moe.expert_up",
        "moe.expert_down", "moe.shared.w_gate", "moe.shared.w_up", "moe.shared.w_down",
        "head.lm_head",
    }
    assert c[("mla.uk", 3, SMALL.nope, SMALL.kv_lora)] == SMALL.n_heads * SMALL.n_layers
    # 3 tokens x top-2 over 8 experts: 6 experts of one row in each MoE layer
    assert c[("moe.expert_up", 1, SMALL.d_model, SMALL.moe_ff)] == 6 * 2


def test_even_route_split():
    for t in (1, 3, 7, 100):
        rows = np.bincount(ref.even_route(t, 3, 8).ravel(), minlength=8)
        assert rows.sum() == 3 * t and rows.max() - rows.min() <= 1


def test_closed_form_published():
    cf = ref.closed_form(ref.PUBLISHED)
    assert round(cf["params"] / 1e9, 1) == 671.0
    assert round(cf["active_params"] / 1e9, 2) == 37.55
    assert round(cf["decode_macs_per_token"] / 1e9, 2) == 36.62


@pytest.mark.parametrize("traced", [False, True])
def test_deepseek_cell_tiny_run_is_correct(spec, tiny, traced):
    """The ``deepseek_v3.oneshot`` cell through the harness on the CPU, at
    published widths with the profiled slice clipped to a CPU size: the
    toggle counts and J/op checks hold on DeepSeek's job set, and a traced
    run reports the per-layer metrics the CPU can read."""
    import json

    from bench import cells, harness

    doc = json.loads((cells.BENCH / "configs" / "deepseek_v3.json").read_text())
    doc["clip"] = tiny["cfg_doc"]["clip"]
    out = harness.run_cell(spec, "deepseek_v3.oneshot", 2**33 + 3, 1.0, traced,
                           require_tpu=False, mix=tiny["mix"], cfg_doc=doc)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["count_mismatch_jobs"]["value"] == 0
    if traced:
        assert {"expand_ms", "profile_ms", "lower_ms", "compiles_per_answer"} <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == {"answer_s", "answer_p90_s", "setup_s"}
