"""CPU tests of the benchmark harness (not part of the repository's tier-1
``testpaths``): ``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``."""

import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices, so codesign shards its profiling as on a 2x2 host
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
# compiles of the tests stay out of any persistent cache
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


@pytest.fixture(scope="session")
def spec():
    from bench import cells

    return cells.load_benchmark()


@pytest.fixture(scope="session")
def tiny():
    """A CPU-sized oneshot cell: the tiny mix and config of ``data/``."""
    return {
        "mix": json.loads((DATA / "tiny_mix.json").read_text()),
        "cfg_doc": json.loads((DATA / "tiny_config.json").read_text()),
    }


@pytest.fixture
def run_tiny(spec, tiny):
    from bench import harness

    def run(traced=False, seconds=1.0, seed=2**33 + 17, **kw):
        return harness.run_cell(
            spec, "mixtral_8x7b.oneshot", seed, seconds, traced,
            require_tpu=False, **{**tiny, **kw},
        )

    return run
