import json

import numpy as np

from bench import cells, traffic


def test_queries_repeat_per_seed():
    mix = cells.load_mix("oneshot")
    seed = 2**40 + 3  # wider than 32 bits, as the driver's seeds are
    assert traffic.make_queries(mix, seed) == traffic.make_queries(mix, seed)


def test_seeds_reorder_the_same_work():
    mix = cells.load_mix("oneshot")
    a = traffic.make_queries(mix, 1)
    b = traffic.make_queries(mix, 2)
    assert [q.ratio for q in a] != [q.ratio for q in b]
    assert sorted(q.ratio for q in a) == sorted(q.ratio for q in b)
    assert [q.traffic_seed for q in a] != [q.traffic_seed for q in b]
    assert min(q.ratio for q in a) > 0.125 and max(q.ratio for q in a) < 48.0


def test_pe_area_scale_strata():
    mix = cells.load_mix("grid_explore")
    qs = traffic.make_queries(mix, 5)
    scales = sorted(q.pe_area_scale for q in qs)
    np.testing.assert_allclose(scales, traffic.log_strata(0.5, 2.0, 8))
    assert all(q.ratio is None and q.traffic_seed is None for q in qs)
    tm, space = traffic.build_inputs(mix, qs[0])
    assert tm.name == "decode_heavy"
    np.testing.assert_allclose(
        space.pe_area_um2, [a * qs[0].pe_area_scale for a in mix["space"]["pe_area_um2"]]
    )


def test_oneshot_inputs_are_the_preset_reratioed():
    mix = cells.load_mix("oneshot")
    q = traffic.make_queries(mix, 9)[0]
    tm, space = traffic.build_inputs(mix, q)
    assert abs(tm.prefill_decode_ratio - q.ratio) < 1e-9 * q.ratio
    assert tm.seed == q.traffic_seed
    assert space.expand().n_points == 40


def test_configs_are_the_registry_entries(spec):
    from bench.harness import _arch
    from repro.configs.registry import get_arch

    for cfg in spec["configs"]:
        doc = cells.load_config(spec, cfg["name"])
        assert _arch(doc) == get_arch(doc["arch"]["name"])
        assert doc["hidden_size"] == doc["arch"]["d_model"]
        assert doc["intermediate_size"] == doc["arch"]["d_ff"]
        assert doc["num_hidden_layers"] == doc["arch"]["n_layers"]
        assert doc["num_key_value_heads"] == doc["arch"]["num_kv_heads"]
        assert doc["vocab_size"] == doc["arch"]["vocab_size"]
        assert doc["num_experts_per_tok"] == doc["arch"]["top_k"]
        arch = get_arch(doc["arch"]["name"])
        if "mamba_dt_rank" in doc:
            assert doc["mamba_dt_rank"] == (arch.mamba_dt_rank or arch.d_model // 16)
            assert doc["mamba_d_state"] == arch.mamba_d_state
            assert doc["mamba_expand"] == arch.mamba_expand


def test_every_cell_finds_its_files(spec):
    for cell in spec["workloads"]:
        cells.load_mix(cell["traffic"])
        cells.load_config(spec, cell["config"])
        assert set(cells.load_limits(cell["name"])) == {
            "count_mismatch_jobs", "jop_plain_gap", "jop_rel_gap"
        }
    for m in spec["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    json.dumps(spec)
