"""The program's host spans (``repro.tracing``): the name prefix, nesting on
the profiler's clock, no jax on numpy-only paths, and the spans and
``profile_stats`` of one small ``codesign`` answer."""

import builtins
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.tracing import PREFIX, span, traced

# every span a codesign answer opens, with the thread it runs on
MAIN_SPANS = {
    "repro.codesign", "repro.expand", "repro.profile", "repro.profile.jobs",
    "repro.profile.setup", "repro.profile.check",
    "repro.profile.synth_wait", "repro.profile.key", "repro.profile.schedule",
    "repro.profile.stack", "repro.profile.collect", "repro.profile.assemble",
    "repro.lower.lower_layout_coeffs", "repro.lower.lower_coding_multipliers",
    "repro.lower.lower_partition_coeffs", "repro.lower.grid_coding_effective",
    "repro.price",
}
WORKER_SPANS = {"repro.profile.synthesize", "repro.profile.dispatch"}


def _trace_events(log_dir):
    """``repro.*`` host events of a trace: (start_ns, end_ns, name, line, stats)."""
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    out = []
    for p, plane in enumerate(ProfileData.from_file(str(path)).planes):
        for li, line in enumerate(plane.lines):
            out.extend(
                (e.start_ns, e.end_ns, e.name, (p, li), dict(list(e.stats)))
                for e in line.events
                if e.name.startswith(PREFIX)
            )
    return sorted(out)


def test_span_prefix_nesting_and_stats(tmp_path):
    import jax

    @traced("outer")
    def outer():
        with span("outer.inner", answer="a1"):
            time.sleep(0.002)

    with jax.profiler.trace(str(tmp_path)):
        outer()
    ev = {name: (s, e, line, stats) for s, e, name, line, stats in _trace_events(tmp_path)}
    assert set(ev) == {"repro.outer", "repro.outer.inner"}
    (os_, oe, oline, _), (is_, ie, iline, istats) = ev["repro.outer"], ev["repro.outer.inner"]
    assert oline == iline
    assert os_ <= is_ < ie <= oe
    assert ie - is_ >= 2e6
    assert istats == {"answer": "a1"}


def test_traced_keeps_the_function():
    @traced("x")
    def f(a, *, b=2):
        """doc"""
        return a + b

    assert f(1) == 3 and f.__name__ == "f" and f.__doc__ == "doc"


def test_span_without_jax_imports_none():
    """Without jax in the process no trace can run: a span is a no-op and
    imports nothing."""
    code = (
        "import sys; from repro.tracing import span\n"
        "with span('x'): pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    src = str(Path(__file__).resolve().parents[1] / "src")
    subprocess.run([sys.executable, "-c", code], check=True, env={"PYTHONPATH": src})


def test_numpy_batch_imports_no_jax(monkeypatch):
    """The numpy-only serial path of ``run_profile_batch`` executes no
    ``import jax``, spans included (lazy jobs open ``profile.synthesize``).
    A first call loads the modules the path imports; the second is watched."""
    from repro.core.pipeline import run_profile_batch
    from repro.core.workloads import Gemm, gemm_job

    def jobs():
        return [gemm_job(Gemm("g", 8, 16, 8), 4, 4, 8, seed=s) for s in range(2)]

    run_profile_batch(jobs(), backend="numpy", use_cache=False)
    seen = []
    real = builtins.__import__

    def spy(name, *args, **kwargs):
        if name == "jax" or name.startswith("jax."):
            seen.append(name)
        return real(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", spy)
    profiles, stats = run_profile_batch(jobs(), backend="numpy", use_cache=False)
    monkeypatch.undo()
    assert seen == []
    assert stats.serial_fallbacks == 2 and all(p is not None for p in profiles)


def test_codesign_spans_and_profile_stats(tmp_path, monkeypatch):
    """One small answer under the profiler: every span of the program, the
    main-thread spans inside ``repro.codesign``, the worker spans inside its
    interval, and ``profile_stats`` of the one profiling batch."""
    import jax

    import repro.core.pipeline as pipeline
    from repro.core.design_space import DesignSpace
    from repro.core.switching import clear_profile_cache
    from repro.layout.coeffs import clear_coeff_cache
    from repro.serving import codesign

    batches = []
    real = pipeline.run_profile_batch

    def spy(jobs, *args, **kwargs):
        jobs = list(jobs)
        batches.append(len(jobs))
        return real(jobs, *args, **kwargs)

    monkeypatch.setattr(pipeline, "run_profile_batch", spy)
    clear_profile_cache()
    clear_coeff_cache()
    space = DesignSpace(
        rows=(8,), cols=(8,), input_bits=(8,), dataflows=("WS", "OS"),
        bus_invert=(False, True),
    )
    with jax.profiler.trace(str(tmp_path)):
        res = codesign(
            "mixtral_8x7b", "decode_heavy", space=space, layouts=("uniform",),
            clip=(16, 64, 32),
        )
    events = _trace_events(tmp_path)
    names = {n for _, _, n, _, _ in events}
    assert MAIN_SPANS | WORKER_SPANS <= names, sorted(MAIN_SPANS | WORKER_SPANS - names)

    (root,) = [(s, e, line, st) for s, e, n, line, st in events if n == "repro.codesign"]
    lo, hi, main, stats = root
    assert stats == {"arch": "mixtral_8x7b", "traffic": "decode_heavy"}
    for s, e, n, line, _ in events:
        assert lo <= s <= e <= hi, n
        if n in MAIN_SPANS:
            assert line == main, n
        else:
            assert n in WORKER_SPANS and line != main, n

    assert batches == [res.profile_stats.jobs]
    assert res.profile_stats.buckets >= 2  # a WS and an OS stream program
    assert np.isfinite(res.j_per_token)


def test_synthesize_span_once_per_recipe(tmp_path, monkeypatch):
    """In one answer the prefetch worker builds each operand recipe once:
    ``repro.profile.synthesize`` opens once per distinct recipe of the
    batch, not once per job."""
    import jax

    import repro.core.pipeline as pipeline
    from repro.core.design_space import DesignSpace
    from repro.core.switching import clear_profile_cache
    from repro.serving import codesign

    recipes = []
    real = pipeline.run_profile_batch

    def spy(jobs, *args, **kwargs):
        jobs = list(jobs)
        recipes.append([job.recipe for job in jobs])
        return real(jobs, *args, **kwargs)

    monkeypatch.setattr(pipeline, "run_profile_batch", spy)
    clear_profile_cache()
    space = DesignSpace(
        rows=(8, 16), cols=(8,), input_bits=(8,), dataflows=("WS", "OS"),
        bus_invert=(False,),
    )
    with jax.profiler.trace(str(tmp_path)):
        res = codesign(
            "mixtral_8x7b", "decode_heavy", space=space, layouts=("uniform",),
            clip=(16, 64, 32),
        )
    (batch,) = recipes
    distinct = len(set(batch))
    assert None not in batch and len(batch) == 3 * distinct  # 3 activity classes
    names = [n for _, _, n, _, _ in _trace_events(tmp_path)]
    assert names.count("repro.profile.synthesize") == distinct
    assert res.profile_stats.synthesized == distinct
    assert res.profile_stats.recipe_hits == 0


@pytest.mark.parametrize("dispatched", [True, False])
def test_collect_span_only_when_dispatched(tmp_path, dispatched):
    """A batch served wholly from the cache opens no ``profile.collect``."""
    import jax

    from repro.core.pipeline import run_profile_batch
    from repro.core.switching import clear_profile_cache
    from repro.core.workloads import Gemm, gemm_job

    def jobs():
        return [gemm_job(Gemm("g", 16, 16, 8), 4, 4, 8, seed=3)]

    clear_profile_cache()
    if not dispatched:
        run_profile_batch(jobs())
    with jax.profiler.trace(str(tmp_path)):
        _, stats = run_profile_batch(jobs())
    names = [n for _, _, n, _, _ in _trace_events(tmp_path)]
    assert ("repro.profile.collect" in names) == dispatched
    assert stats.cache_hits == (0 if dispatched else 1)
    assert names.count("repro.profile.key") == (2 if dispatched else 1)


def test_expand_span_carries_job_set_census(tmp_path):
    """``repro.expand`` records the job set's GEMM class count and its MAC
    share per block family, as ``ServingJobSet.family_shares`` gives them."""
    import jax

    from repro.configs.registry import get_arch
    from repro.serving import get_preset, weighted_gemms

    with jax.profiler.trace(str(tmp_path)):
        js = weighted_gemms(get_arch("deepseek_v3"), get_preset("decode_heavy"))
    (stats,) = [st for _, _, n, _, st in _trace_events(tmp_path) if n == "repro.expand"]
    shares = js.family_shares()
    assert list(shares) == ["mla", "dense", "moe.routed", "moe.shared", "head"]
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-12)
    assert stats == {"gemm_classes": len(js.gemms), **shares}


def test_coding_lowering_counters_in_trace(tmp_path):
    """``repro.lower.grid_coding_effective`` carries its two counters on the
    profiler's clock: coded entries and the distinct pairs evaluated."""
    import jax

    from repro.core.design_space import DesignSpace
    from repro.layout.coeffs import clear_coeff_cache, grid_coding_effective

    grid = DesignSpace(
        rows=(8, 16), cols=(8, 16), input_bits=(8,), dataflows=("WS", "OS"),
        bus_invert=(False, True),
    ).expand()
    a_v = np.tile([0.25, 0.5], (3, grid.n_points // 2))
    clear_coeff_cache()
    with jax.profiler.trace(str(tmp_path)):
        grid_coding_effective(grid, a_v)
    (stats,) = [
        st for _, _, n, _, st in _trace_events(tmp_path)
        if n == "repro.lower.grid_coding_effective"
    ]
    assert stats["coded_elements"] == 3 * grid.n_points // 2
    assert 0 < stats["coded_evaluated"] <= stats["coded_elements"]
