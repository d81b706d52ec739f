"""A run with the timed path broken underneath comes out as not correct,
once for each fault a cell of this benchmark can have."""

import numpy as np
import pytest


def _patch(monkeypatch, module, attr, make):
    import importlib

    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))


def test_count_altered_where_produced(run_tiny, monkeypatch):
    def make(orig):
        def reduce(h_parts, v_parts, num_tasks):
            h, v = orig(h_parts, v_parts, num_tasks)
            v = v.copy()
            v[0] += 1
            return h, v

        return reduce

    _patch(monkeypatch, "repro.kernels.activity_profile.batch", "reduce_bucket_parts", make)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["count_mismatch_jobs"]["value"] > 0


def test_jop_answer_altered_where_produced(run_tiny, monkeypatch):
    def make(orig):
        def lowered(*key):
            fn = orig(*key)

            def run(*args):
                out = dict(fn(*args))
                out["j_per_mac_robust"] = out["j_per_mac_robust"] * 1.001
                return out

            return run

        return lowered

    _patch(monkeypatch, "repro.layout.power", "_jitted_coeff_eval", make)
    out = run_tiny()
    assert not out["correct"]
    for name in ("jop_plain_gap", "jop_rel_gap"):
        assert out["checks"][name]["value"] > out["checks"][name]["limit"]


def test_half_the_batch_left_out(run_tiny, monkeypatch):
    def make(orig):
        def run_profile_batch(jobs, *args, **kwargs):
            jobs = list(jobs)
            half = len(jobs) // 2
            profiles, stats = orig(jobs[:half], *args, **kwargs)
            rest = [profiles[i % half] for i in range(len(jobs) - half)]
            return list(profiles) + rest, stats

        return run_profile_batch

    _patch(monkeypatch, "repro.core.pipeline", "run_profile_batch", make)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["count_mismatch_jobs"]["value"] > 0


def test_exchange_between_chips_left_out(run_tiny, tiny, monkeypatch):
    """The four-chip cell's fault: a shard's partial counts never gathered.
    Profiled at a clip whose buckets hold enough tasks to be sharded."""
    import jax

    devices = jax.local_devices()
    assert len(devices) == 4, "the tests run on four host devices"
    dropped = []

    def make(orig):
        def bucket_toggle_parts(*args, device=None, **kwargs):
            h, v, n = orig(*args, device=device, **kwargs)
            if device is not None and device != devices[0]:
                dropped.append(device)
                v = v * 0  # this shard's partial counts never reach the host
            return h, v, n

        return bucket_toggle_parts

    _patch(monkeypatch, "repro.kernels.activity_profile.batch", "bucket_toggle_parts", make)
    out = run_tiny(cfg_doc={**tiny["cfg_doc"], "clip": [16, 128, 64]})
    assert dropped, "no bucket was sharded over the devices"
    assert not out["correct"]
    assert out["checks"]["count_mismatch_jobs"]["value"] > 0


def test_stale_answer(run_tiny, monkeypatch):
    """The answer of the previous question returned for this one."""
    last = []

    def make(orig):
        def codesign(*args, **kwargs):
            res = orig(*args, **kwargs)
            out = last[-1] if last else res
            last.append(res)
            return out

        return codesign

    _patch(monkeypatch, "repro.serving.codesign", "codesign", make)
    out = run_tiny()
    assert not out["correct"]


def test_lowering_fault_shared_by_the_float64_path(run_tiny, monkeypatch):
    """A fault in the host lowering reaches the program's float64 path as
    well, so only the plain reference sees it."""
    def make(orig):
        def lower_partition_coeffs(*args, **kwargs):
            entry = orig(*args, **kwargs)
            entry.host["utilization"] = entry.host["utilization"] * 0.98
            return entry

        return lower_partition_coeffs

    _patch(monkeypatch, "repro.core.objective", "lower_partition_coeffs", make)
    out = run_tiny()
    assert not out["correct"]
    c = out["checks"]
    assert c["jop_rel_gap"]["value"] <= c["jop_rel_gap"]["limit"]
    assert c["jop_plain_gap"]["value"] > c["jop_plain_gap"]["limit"]
