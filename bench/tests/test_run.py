import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells

ROOT = cells.ROOT


def test_no_tpu_no_result(capsys):
    """On the CPU the harness reports no device metric: it exits nonzero
    and prints nothing on standard output."""
    sys.path.insert(0, str(ROOT / "bench"))
    import run

    rc = run.main(["--workload", "mixtral_8x7b.oneshot", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out == ""
    assert "TPU" in out.err


def test_without_the_program_no_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ fails."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mixtral_8x7b.oneshot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_run_is_correct(run_tiny, spec, traced):
    out = run_tiny(traced=traced)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {
        "count_mismatch_jobs", "jop_plain_gap", "jop_rel_gap", "failed_answers"
    }
    assert out["checks"]["count_mismatch_jobs"]["value"] == 0
    kind = "per_layer" if traced else "end_to_end"
    want = {m["name"] for m in cells.cell_metrics(spec, "mixtral_8x7b.oneshot", kind)}
    got = set(out["metrics"])
    assert got <= want
    if traced:
        # no device trace on the CPU: the device readers leave their metric out
        assert {"expand_ms", "profile_ms", "lower_ms", "compiles_per_answer",
                "profile_cache_hit_share"} <= got
        assert not {"device_idle_share", "jop_device_ms", "profile_kernel_hbm_share"} & got
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
    else:
        assert got == want
        m = out["metrics"]
        assert m["answer_p90_s"]["value"] >= 0 and m["answer_s"]["value"] > 0
        assert m["setup_s"]["value"] > 0
    json.dumps(out)


def test_every_answer_compiles_once(run_tiny):
    """The per-call backend spin-up of the profiling pipeline shows as one
    compile per answer inside the window."""
    out = run_tiny(traced=True)
    assert out["metrics"]["compiles_per_answer"]["value"] == pytest.approx(1.0)
