"""Serving-traffic subsystem: registry expansion, traffic model, J/token.

Covers the three layers of ``repro.serving`` plus the shared decode-shape
authority in ``launch.specs``:

  * every registry config expands to a non-empty, positive-shape GEMM job
    set in both regimes, with MoE routing sparsity in (0, 1];
  * decode shapes can no longer drift: ``decode_batch_specs`` and the
    serving expansion both derive M from ``launch.specs.token_shape``;
  * the seeded traffic model is bit-deterministic, MAC-share weights sum
    to 1, and sweeping the prefill:decode ratio MOVES the design optimum
    (regression-pinned);
  * the J/token aggregation slot prices exactly j_per_mac * MACs/token
    and refuses half-configured evaluations.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, SHAPES, get_arch
from repro.core.design_space import DesignSpace
from repro.core.objective import evaluate_fleet_objective
from repro.core.workloads import (
    Gemm,
    gemm_profile_seed,
    measured_design_gemm_activities,
)
from repro.launch.specs import decode_batch_specs, token_shape
from repro.serving import (
    PRESETS,
    ServingGemm,
    TrafficModel,
    expand_arch,
    expand_shape,
    get_preset,
    regime_tokens,
    routing_sparsity,
    sample_requests,
    traffic_classes,
    weighted_gemms,
)

MOE_ARCHS = [a for a in ARCH_IDS if get_arch(a).num_experts > 1]
DENSE_ARCHS = [a for a in ARCH_IDS if get_arch(a).num_experts <= 1]


# ---------------------------------------------------------------------------
# Registry expansion (every config, both regimes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("regime,batch,seq", [("prefill", 4, 512), ("decode", 64, 1)])
def test_every_config_expands(arch, regime, batch, seq):
    cfg = get_arch(arch)
    jobs = expand_arch(cfg, regime, batch, seq)
    assert jobs, f"{arch}: empty {regime} job set"
    t = regime_tokens(cfg, regime, batch, seq)
    for j in jobs:
        assert min(j.gemm.m, j.gemm.k, j.gemm.n) >= 1, (arch, j.block)
        assert j.count >= 1 and j.macs > 0, (arch, j.block)
        assert j.regime == regime
        if j.input_density is not None:
            assert 0.0 < j.input_density <= 1.0
        # every non-expert GEMM runs at the regime's token batch
        if not j.block.startswith("moe.expert"):
            assert j.gemm.m == t, (arch, j.block, j.gemm.m, t)
    blocks = {j.block for j in jobs}
    assert "head.lm_head" in blocks


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_routing_sparsity_in_unit_interval(arch):
    cfg = get_arch(arch)
    s = routing_sparsity(cfg)
    assert 0.0 < s <= 1.0
    if cfg.num_experts > 1:
        assert s == cfg.top_k / cfg.num_experts < 1.0
    else:
        assert s == 1.0


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_moe_effective_expert_batch(arch):
    cfg = get_arch(arch)
    t = 256
    jobs = expand_arch(cfg, "prefill", 1, t)
    experts = [j for j in jobs if j.block.startswith("moe.expert")]
    assert experts, f"{arch}: no expert GEMMs"
    m_e = max(1, round(t * routing_sparsity(cfg)))
    assert all(j.gemm.m == m_e for j in experts)
    assert all(j.count % cfg.num_experts == 0 for j in experts)
    router = [j for j in jobs if j.block == "moe.router"]
    assert router and all(j.gemm.m == t and j.gemm.n == cfg.num_experts for j in router)


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_routed_rows_equal_tokens_times_top_k(arch):
    """The even split prices exactly t * top_k routed expert rows per MoE
    layer, over at most E experts, at every token batch and in both regimes."""
    cfg = get_arch(arch)
    moe_layers = sum(mlp == "moe" for _, mlp in cfg.stage_pattern) * cfg.n_stages
    for regime in ("prefill", "decode"):
        for t in range(1, 513):
            jobs = expand_arch(cfg, regime, t) if regime == "decode" else expand_arch(cfg, regime, 1, t)
            gate = [j for j in jobs if j.block == "moe.expert_gate"]
            assert sum(j.gemm.m * j.count for j in gate) == t * cfg.top_k * moe_layers
            assert sum(j.count for j in gate) == min(cfg.num_experts, t * cfg.top_k) * moe_layers
            assert max(j.gemm.m for j in gate) - min(j.gemm.m for j in gate) <= 1


@pytest.mark.parametrize("shape_id", sorted(SHAPES))
def test_registry_shape_cells_expand(shape_id):
    shape = SHAPES[shape_id]
    for arch in ("mixtral_8x7b", "qwen3_8b"):
        jobs = expand_shape(get_arch(arch), shape)
        assert jobs and all(j.macs > 0 for j in jobs)
        want = "decode" if shape.kind == "decode" else "prefill"
        assert all(j.regime == want for j in jobs)


def test_expand_contract_errors():
    cfg = get_arch("qwen3_8b")
    with pytest.raises(ValueError, match="regime"):
        expand_arch(cfg, "train", 1, 16)
    with pytest.raises(ValueError, match="batch"):
        expand_arch(cfg, "prefill", 0, 16)
    with pytest.raises(ValueError, match="count"):
        ServingGemm(Gemm("x", 1, 1, 1), "b", "decode", count=0)
    with pytest.raises(ValueError, match="non-positive"):
        ServingGemm(Gemm("x", 1, 0, 1), "b", "decode", count=1)


# ---------------------------------------------------------------------------
# Decode-shape drift: launch specs and serving expansion share one authority
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_specs_match_token_shape(arch):
    cfg = get_arch(arch)
    shape = SHAPES["decode_32k"]
    specs, _axes = decode_batch_specs(cfg, shape)
    assert tuple(specs["tokens"].shape) == token_shape(cfg, shape.global_batch, 1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_expansion_matches_decode_specs(arch):
    cfg = get_arch(arch)
    b = SHAPES["decode_32k"].global_batch
    specs, _axes = decode_batch_specs(cfg, shape=SHAPES["decode_32k"])
    tok = tuple(specs["tokens"].shape)
    m = tok[0] * tok[1]  # codebook streams share one position
    assert regime_tokens(cfg, "decode", b) == m
    jobs = expand_arch(cfg, "decode", b)
    non_expert = [j for j in jobs if not j.block.startswith("moe.expert")]
    assert all(j.gemm.m == m for j in non_expert)
    # decode ignores any stray seq_len: M is the decode-step token count
    assert expand_arch(cfg, "decode", b, 999)[0].gemm.m == m


def test_prefill_tokens_are_batch_times_seq():
    for arch in ("qwen3_8b", "musicgen_medium"):
        cfg = get_arch(arch)
        assert regime_tokens(cfg, "prefill", 3, 128) == 3 * 128


# ---------------------------------------------------------------------------
# Traffic model: seeded determinism, weight invariants
# ---------------------------------------------------------------------------


def test_sample_requests_deterministic():
    tm = get_preset("balanced")
    a = sample_requests(tm)
    b = sample_requests(tm)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = sample_requests(dataclasses.replace(tm, seed=1))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_traffic_classes_invariants(preset):
    tm = get_preset(preset)
    classes = traffic_classes(tm)
    regimes = {tc.regime for tc in classes}
    assert regimes == {"prefill", "decode"}
    prompts, gens, _ = sample_requests(tm)
    window_s = tm.n_samples / tm.qps
    tok = sum(tc.tokens_per_s for tc in classes)
    # every served token (unpadded) is attributed to exactly one class
    assert tok == pytest.approx(float(prompts.sum() + gens.sum()) / window_s)
    for tc in classes:
        assert tc.batch >= 1 and tc.seq_len >= 1
        assert tc.tokens_per_s > 0 and tc.execs_per_s > 0
        if tc.regime == "decode":
            assert tc.seq_len == 1 and tc.batch <= tm.max_decode_batch
        else:
            assert tc.batch <= tm.max_prefill_batch
            assert tc.seq_len & (tc.seq_len - 1) == 0  # power of two


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_jobset_weights_sum_to_one(preset):
    js = weighted_gemms(get_arch("mixtral_8x7b"), get_preset(preset))
    w = np.asarray(js.weights)
    assert w.sum() == pytest.approx(1.0, abs=1e-12)
    assert (w > 0).all()
    assert js.macs_per_token > 0
    # regime weights partition the total
    dec = js.regime_weights("decode").sum()
    pre = js.regime_weights("prefill").sum()
    assert dec + pre == pytest.approx(1.0, abs=1e-12)


def test_jobset_bit_deterministic():
    cfg = get_arch("jamba_v01_52b")
    tm = get_preset("decode_heavy")
    a = weighted_gemms(cfg, tm)
    b = weighted_gemms(cfg, tm)
    assert a.gemms == b.gemms
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))
    assert np.array_equal(np.asarray(a.mac_rate), np.asarray(b.mac_rate))
    assert a.macs_per_token == b.macs_per_token
    c = weighted_gemms(cfg, dataclasses.replace(tm, seed=3))
    assert not np.array_equal(np.asarray(a.weights), np.asarray(c.weights))


def test_jobset_mac_conservation():
    cfg = get_arch("qwen3_8b")
    tm = get_preset("balanced")
    js = weighted_gemms(cfg, tm)
    total = 0.0
    for tc in traffic_classes(tm):
        step = sum(sg.macs for sg in expand_arch(cfg, tc.regime, tc.batch, tc.seq_len))
        total += tc.execs_per_s * step
    assert float(np.asarray(js.mac_rate).sum()) == pytest.approx(total, rel=1e-12)
    assert js.macs_per_token == pytest.approx(total / js.tokens_per_s, rel=1e-12)


def test_preset_regime_shares():
    cfg = get_arch("mixtral_8x7b")
    dec_share = lambda p: float(
        weighted_gemms(cfg, get_preset(p)).regime_weights("decode").sum()
    )
    assert dec_share("decode_heavy") > 0.6
    assert dec_share("prefill_heavy") < 0.1
    assert dec_share("decode_heavy") > dec_share("balanced") > dec_share("prefill_heavy")


def test_with_ratio_rescales_gen_mean():
    tm = get_preset("balanced")
    t2 = tm.with_ratio(4.0)
    assert t2.prefill_decode_ratio == pytest.approx(4.0)
    assert t2.prompt_len == tm.prompt_len
    with pytest.raises(ValueError):
        tm.with_ratio(0.0)


def test_traffic_model_validation():
    with pytest.raises(ValueError, match="qps"):
        TrafficModel("x", qps=0.0, prompt_len=(64.0, 0.5), gen_len=(64.0, 0.5))
    with pytest.raises(ValueError, match="gen_len"):
        TrafficModel("x", qps=1.0, prompt_len=(64.0, 0.5), gen_len=(0.5, 0.5))
    with pytest.raises(KeyError):
        get_preset("nope")


# ---------------------------------------------------------------------------
# Ratio sweep moves the design optimum (regression-pinned)
# ---------------------------------------------------------------------------


def test_ratio_sweep_moves_optimum():
    cfg = get_arch("mixtral_8x7b")
    tm = get_preset("balanced")
    grid = DesignSpace(
        rows=(16, 32),
        cols=(8, 32, 128),
        input_bits=(16,),
        dataflows=("WS", "OS"),
        bus_invert=(False, True),
    ).expand()
    families = ("uniform", "serpentine2", "pods2x2", "pods4x4")

    cells, shares = {}, {}
    for ratio in (0.05, 4.0, 48.0):
        js = weighted_gemms(cfg, tm.with_ratio(ratio))
        shares[ratio] = float(js.regime_weights("decode").sum())
        rng = np.random.default_rng(7)
        a_h = rng.uniform(0.1, 0.4, (len(js.gemms), grid.n_points))
        a_v = rng.uniform(0.2, 0.6, (len(js.gemms), grid.n_points))
        ev = evaluate_fleet_objective(
            grid, a_h, a_v, js.gemms, layouts=families, weights=js.weights,
            macs_per_token=js.macs_per_token,
        )
        j = np.asarray(ev.j_per_mac_robust)
        cells[ratio] = tuple(
            int(i) for i in np.unravel_index(np.argmin(j), j.shape)
        )
    # longer generations (lower ratio) -> more decode MAC share, monotone
    assert shares[0.05] > shares[4.0] > shares[48.0]
    assert shares[0.05] == pytest.approx(0.8469, abs=0.05)
    assert shares[48.0] == pytest.approx(0.0172, abs=0.02)
    # the optimum must MOVE across the sweep: a decode-dominated second
    # picks a different (family, point) cell than a prefill-dominated one
    assert cells[0.05] != cells[48.0], cells


# ---------------------------------------------------------------------------
# J/token aggregation slot
# ---------------------------------------------------------------------------


def _tiny_eval(macs_per_token=None):
    grid = DesignSpace(
        rows=(8,), cols=(8, 16), input_bits=(8,), dataflows=("WS",)
    ).expand()
    gemms = [Gemm("a", 64, 32, 16), Gemm("b", 8, 32, 16)]
    rng = np.random.default_rng(0)
    a_h = rng.uniform(0.1, 0.4, (2, grid.n_points))
    a_v = rng.uniform(0.2, 0.6, (2, grid.n_points))
    return evaluate_fleet_objective(
        grid, a_h, a_v, gemms, layouts=("uniform",),
        macs_per_token=macs_per_token,
    )


def test_j_per_token_is_j_per_mac_times_macs_per_token():
    ev = _tiny_eval(macs_per_token=1.5e9)
    assert ev.macs_per_token == 1.5e9
    got = np.asarray(ev.j_per_token_robust)
    want = np.asarray(ev.j_per_mac_robust) * 1.5e9
    assert np.array_equal(got, want)
    assert np.isfinite(got).any()


def test_j_per_token_requires_both_halves():
    ev = _tiny_eval()  # priced J/op, no macs_per_token
    with pytest.raises(ValueError, match="macs_per_token"):
        _ = ev.j_per_token_robust
    with pytest.raises(ValueError, match="positive"):
        _tiny_eval(macs_per_token=0.0)


def test_serving_jobset_through_objective():
    js = weighted_gemms(get_arch("qwen3_8b"), get_preset("decode_heavy"))
    grid = DesignSpace(
        rows=(16,), cols=(8, 16), input_bits=(16,), dataflows=("WS", "OS")
    ).expand()
    rng = np.random.default_rng(1)
    a_h = rng.uniform(0.1, 0.4, (len(js.gemms), grid.n_points))
    a_v = rng.uniform(0.2, 0.6, (len(js.gemms), grid.n_points))
    ev = evaluate_fleet_objective(
        grid, a_h, a_v, js.gemms, layouts=("uniform", "pods2x2"),
        weights=js.weights, macs_per_token=js.macs_per_token,
    )
    jpt = np.asarray(ev.j_per_token_robust)
    assert jpt.shape == (2, grid.n_points)
    assert np.isfinite(jpt).any() and (jpt[np.isfinite(jpt)] > 0).all()


# ---------------------------------------------------------------------------
# Measured activities over a GEMM job set: dedup + determinism
# ---------------------------------------------------------------------------


def test_gemm_profile_seed_content_keyed():
    g1 = Gemm("dec.q", 64, 4096, 4096)
    g2 = Gemm("pre.q", 64, 4096, 4096)  # same content, different name
    clip = (128, 512, 256)
    assert gemm_profile_seed(g1, clip=clip) == gemm_profile_seed(g2, clip=clip)
    # clipped dims key the seed: 4096 and 600 both clip to 512
    g3 = Gemm("x", 64, 600, 4096)
    assert gemm_profile_seed(g1, clip=clip) == gemm_profile_seed(g3, clip=clip)
    assert gemm_profile_seed(g1, clip=clip) != gemm_profile_seed(
        g1, clip=clip, density=0.5
    )
    assert gemm_profile_seed(g1, clip=None) != gemm_profile_seed(g3, clip=None)


def test_measured_gemm_activities_dedup_and_determinism():
    grid = DesignSpace(
        rows=(8,), cols=(8,), input_bits=(8,), dataflows=("WS", "OS")
    ).expand()
    clip = (16, 32, 16)
    gemms = [
        Gemm("a", 16, 32, 16),
        Gemm("b", 999, 4096, 777),  # clips to the same operands as "a"
        Gemm("c", 4, 32, 16),
    ]
    a_h, a_v, stats = measured_design_gemm_activities(
        grid, gemms, clip=clip, return_stats=True
    )
    assert a_h.shape == a_v.shape == (3, grid.n_points)
    assert ((0 <= a_h) & (a_h <= 1)).all() and ((0 <= a_v) & (a_v <= 1)).all()
    # identical clipped content -> identical activity rows (profiled once)
    assert np.array_equal(a_h[0], a_h[1]) and np.array_equal(a_v[0], a_v[1])
    assert not np.array_equal(a_h[0], a_h[2])
    b_h, b_v = measured_design_gemm_activities(grid, gemms, clip=clip)
    assert np.array_equal(a_h, b_h) and np.array_equal(a_v, b_v)
