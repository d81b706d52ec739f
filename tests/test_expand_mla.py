"""Latent attention, leading dense layers and routed expert rows in the
serving expander, checked against the plain reference
``bench/mla_reference.py``: the GEMM census of the reference's jaxpr equals
``expand_arch`` class by class, at a small size and, traced abstractly, at
DeepSeek-V3's published widths."""

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro.configs.registry import ArchConfig, get_arch
from repro.serving import expand_arch, weighted_gemms

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import cells, traffic  # noqa: E402
from bench import mla_reference as ref  # noqa: E402

TOKENS = (1, 7, 32, 256)


def dims(cfg) -> ref.Dims:
    return ref.Dims(
        d_model=cfg.d_model, n_heads=cfg.num_heads, q_lora=cfg.q_lora_rank,
        kv_lora=cfg.kv_lora_rank, nope=cfg.head_dim, rope=cfg.qk_rope_head_dim,
        v=cfg.v_head_dim, d_ff=cfg.d_ff, moe_ff=cfg.moe_d_ff, n_experts=cfg.num_experts,
        top_k=cfg.top_k, n_shared=cfg.num_shared_experts, vocab=cfg.vocab_size,
        n_layers=cfg.n_layers, first_k_dense=cfg.first_k_dense,
    )


def expander_census(cfg, regime: str, t: int) -> Counter:
    jobs = expand_arch(cfg, regime, t) if regime == "decode" else expand_arch(cfg, regime, 1, t)
    out = Counter()
    for j in jobs:
        out[(j.block, j.gemm.m, j.gemm.k, j.gemm.n)] += j.count
    return out


_CENSUS: dict = {}


def reference_census(dm: ref.Dims, regime: str, t: int) -> Counter:
    key = (dm, regime, t)
    if key not in _CENSUS:
        _CENSUS[key] = ref.step_census(dm, regime, t)
    return _CENSUS[key]


def test_registry_is_the_published_config():
    doc = json.loads((ROOT / "bench/configs/deepseek_v3.json").read_text())
    cfg = get_arch("deepseek-v3")
    assert dims(cfg) == ref.from_hf(doc) == ref.PUBLISHED
    assert ArchConfig(**doc["arch"]) == cfg


@pytest.mark.parametrize("regime", ["prefill", "decode"])
@pytest.mark.parametrize("t", TOKENS)
def test_expander_equals_reference_census_reduced(regime, t):
    cfg = get_arch("deepseek_v3").reduced()
    assert cfg.first_k_dense == 1 and cfg.v_head_dim != cfg.head_dim
    assert expander_census(cfg, regime, t) == reference_census(dims(cfg), regime, t)


@pytest.mark.parametrize("regime", ["prefill", "decode"])
@pytest.mark.parametrize("t", TOKENS)
def test_expander_equals_reference_census_published(regime, t):
    cfg = get_arch("deepseek_v3")
    got = expander_census(cfg, regime, t)
    assert got == reference_census(ref.PUBLISHED, regime, t)
    blocks = {b for b, *_ in got}
    if regime == "prefill":
        assert "mla.kv_b" in blocks and not {"mla.uk", "mla.uv"} & blocks
    else:
        assert {"mla.uk", "mla.uv"} <= blocks and "mla.kv_b" not in blocks


def test_decode_macs_per_token():
    cfg = get_arch("deepseek_v3")
    macs = sum(j.macs for j in expand_arch(cfg, "decode", 1))
    assert macs == ref.closed_form(ref.PUBLISHED)["decode_macs_per_token"]
    assert round(macs / 1e9, 2) == 36.62


def test_closed_form_counts_match_the_registry():
    cfg = get_arch("deepseek_v3")
    cf = ref.closed_form(ref.PUBLISHED)
    assert cfg.param_count() == cf["params"]
    assert cfg.active_param_count() == cf["active_params"]
    assert round(cf["params"] / 1e9, 1) == 671.0
    assert round(cf["active_params"] / 1e9, 2) == 37.55


@pytest.mark.parametrize("seed", [7, 2**33 + 5])
def test_oneshot_jobsets_equal_census(seed):
    """Every query of the benchmark's ``oneshot`` mix: the job set of
    ``weighted_gemms`` holds the reference census's classes, each at the
    MAC rate the census gives over the traffic classes."""
    spec = cells.load_benchmark()
    cfg = ArchConfig(**cells.load_config(spec, "deepseek_v3")["arch"])
    mix = cells.load_mix("oneshot")
    for q in traffic.make_queries(mix, seed):
        tm, _ = traffic.build_inputs(mix, q)
        js = weighted_gemms(cfg, tm)
        want: dict = {}
        for tc in js.classes:
            t = tc.batch * tc.seq_len
            for (block, m, k, n), count in reference_census(ref.PUBLISHED, tc.regime, t).items():
                key = (f"{tc.regime[:3]}.{block}", m, k, n)
                want[key] = want.get(key, 0.0) + tc.execs_per_s * count * m * k * n
        got = {(g.name, g.m, g.k, g.n): r for g, r in zip(js.gemms, js.mac_rate)}
        assert set(got) == set(want), q
        np.testing.assert_allclose([got[k] for k in want], list(want.values()), rtol=1e-12)


def test_leading_dense_layers():
    cfg = get_arch("deepseek_v3")
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_stages) == (61, 3, 58)
    dense = [j for j in expand_arch(cfg, "decode", 4) if j.block.startswith("mlp.")]
    assert {j.count for j in dense} == {3} and all(j.gemm.k * j.gemm.n == 7168 * 18432 for j in dense)
    # the pattern's mixer runs in every layer, leading ones included
    q_a = [j for j in expand_arch(cfg, "decode", 4) if j.block == "mla.q_a"]
    assert [j.count for j in q_a] == [61]
    assert ArchConfig(**{**cfg.__dict__, "stage_pattern": [["mla", "moe"]]}).stage_pattern == (("mla", "moe"),)
    with pytest.raises(ValueError, match="first_k_dense"):
        ArchConfig(**{**cfg.__dict__, "first_k_dense": 61})
    with pytest.raises(ValueError, match="multiple"):
        ArchConfig(**{**cfg.__dict__, "stage_pattern": (("mla", "moe"),) * 3})
