"""End-to-end validation against the paper's own claims (Section IV):

  * B_h=16, B_v=37 at 32x32 / int16,
  * power-optimal aspect ratio W/H = 3.8,
  * interconnect power saving 9.1%, total 2.1% (ResNet50 average),
  * simulated switching activities in the paper's measured band with
    a_v > a_h and per-layer a_h ordered by input density,
  * Table I conv->GEMM lowering dimensions.
"""

import numpy as np
import pytest

from repro.core.energy import average_comparison, compare_sym_asym
from repro.core.floorplan import (
    BusActivity,
    SystolicArrayGeometry,
    optimal_aspect_power,
)
from repro.core.quant import dequantize, quantize_symmetric
from repro.core.switching import combine_profiles
from repro.core.workloads import RESNET50_TABLE1, conv_to_gemm, profile_conv_layer
from repro.serving.expand import expand_arch

GEOM = SystolicArrayGeometry.paper_32x32()
PAPER_ACT = BusActivity.paper_resnet50()


def test_headline_numbers():
    assert optimal_aspect_power(GEOM, PAPER_ACT) == pytest.approx(3.8, abs=0.05)
    c = compare_sym_asym(GEOM, PAPER_ACT)
    assert c.interconnect_saving == pytest.approx(0.091, abs=0.002)
    assert c.total_saving == pytest.approx(0.021, abs=0.002)


def test_table1_gemm_lowering():
    dims = {g.name: g for g in map(conv_to_gemm, RESNET50_TABLE1)}
    # L1: K=1, H=W=56, C=256, M=64 -> (3136, 256) x (256, 64)
    assert (dims["L1"].m, dims["L1"].k, dims["L1"].n) == (3136, 256, 64)
    # L2: K=3, H=W=28, C=128, M=128 -> (784, 1152) x (1152, 128)
    assert (dims["L2"].m, dims["L2"].k, dims["L2"].n) == (784, 1152, 128)
    # L6: K=3, H=W=14, C=256, M=256 -> (196, 2304, 256)
    assert (dims["L6"].m, dims["L6"].k, dims["L6"].n) == (196, 2304, 256)


@pytest.mark.slow
def test_simulated_activities_in_paper_band():
    """Synthetic-input profiling lands in the paper's regime: a_h in the
    0.15-0.35 band, a_v in 0.3-0.55, and a_v > a_h for EVERY layer."""
    profiles = [
        profile_conv_layer(layer, max_tiles=4, max_stream=128, seed=i)
        for i, layer in enumerate(RESNET50_TABLE1)
    ]
    for p in profiles:
        assert p.a_v > p.a_h
    avg = combine_profiles(profiles)
    assert 0.1 < avg.a_h < 0.4
    assert 0.25 < avg.a_v < 0.6
    # denser-input layers toggle more horizontally (paper's per-layer spread)
    by_density = sorted(zip(RESNET50_TABLE1, profiles), key=lambda t: t[0].input_density)
    assert by_density[0][1].a_h < by_density[-1][1].a_h


@pytest.mark.slow
def test_end_to_end_simulated_savings_positive():
    """Full pipeline on simulated data (no paper constants): per-layer asym
    floorplan still saves interconnect power on every Table I layer."""
    profiles = [
        profile_conv_layer(layer, max_tiles=3, max_stream=96, seed=i)
        for i, layer in enumerate(RESNET50_TABLE1)
    ]
    avg = combine_profiles(profiles).as_bus_activity()
    comps = [
        compare_sym_asym(GEOM, p.as_bus_activity(), design_act=avg)
        for p in profiles
    ]
    for c in comps:
        assert c.interconnect_saving > 0.02
    agg = average_comparison(comps)
    assert 0.04 < agg["interconnect_saving"] < 0.15
    assert 0.005 < agg["total_saving"] < 0.04


def test_quantization_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 64))
    for bits in (8, 16):
        q = quantize_symmetric(x, bits)
        err = np.max(np.abs(dequantize(q) - x))
        assert err <= q.scale * 0.5 + 1e-12
        assert np.max(np.abs(q.values)) <= 2 ** (bits - 1) - 1


def test_llm_gemm_extraction():
    """Beyond-paper: the SA analysis consumes LLM layer GEMMs too."""
    from repro.configs.registry import get_arch

    jobs = expand_arch(get_arch("yi_6b"), "prefill", batch=1, seq_len=128)
    blocks = {j.block for j in jobs}
    want = {"attn.q_proj", "attn.k_proj", "attn.o_proj", "mlp.w_up", "head.lm_head"}
    assert want <= blocks
    q = next(j.gemm for j in jobs if j.block == "attn.q_proj")
    assert (q.m, q.k, q.n) == (128, 4096, 4096)
    mixtral = get_arch("mixtral_8x7b")
    moe = expand_arch(mixtral, "prefill", batch=1, seq_len=128)
    rows = sum(j.gemm.m * j.count for j in moe if j.block == "moe.expert_up")
    assert rows == 128 * 2 * mixtral.n_layers  # top-2 routed rows in every layer
