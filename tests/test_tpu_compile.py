"""Compile-only rehearsal of the activity-profiling Pallas kernels for a TPU
v5e that is described, not attached: every kernel must lower through Mosaic
(a ``tpu_custom_call`` in the compiled program) at codesign's default shapes
and at one full-width Mixtral expert-FFN shape (K = 4096, N = 14336).

Interpret mode cannot see the TPU tiling and memory rules these compiles
enforce. Nothing runs, so results are covered by the interpret-mode tests
and by ``chip_smoke.py`` on the chip. The names that profiles know the
device programs by are checked in the compiled text too: the kernels' op
names, and the fused J/op program's name (a CPU lowering).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compile_cache import persistent_cache_disabled
from repro.kernels.activity_profile import kernel as K


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # keep the compiler's logs off disk
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # entries compiled for a detached chip could not be read back
        with persistent_cache_disabled():
            yield SingleDeviceSharding(topo.devices[0])


def _tasks(rows, cols, b_v, tasks, strips, t_seg1=129):
    shapes = [(strips, t_seg1, rows), (tasks // 2, rows, cols), (tasks,), (tasks,), (tasks,)]
    return (
        lambda *a: K.activity_profile_pallas_tasks(*a, rows=rows, cols=cols, b_v=b_v),
        shapes,
    )


def _strips(strips, t_seg1=129, lanes=64):
    return (
        lambda s: K.stream_strips_toggles_pallas(s, bits=16),
        [(strips, t_seg1, lanes)],
    )


def _operand_stream(t, lanes):
    block_t = min(K.choose_block_t(1, lanes), t)
    return (
        lambda x: K.operand_stream_toggles_pallas(x, bits=16, block_t=block_t),
        [(t, lanes)],
    )


def _per_gemm(m, k, n, rows, cols):
    block_t = min(K.choose_block_t(rows, cols), m)
    k_pad, n_pad = -(-k // rows) * rows, -(-n // cols) * cols
    return (
        lambda a, w: K.activity_profile_pallas(
            a, w, rows=rows, cols=cols, k=k, b_h=16, b_v=37, block_t=block_t
        ),
        [(-(-m // block_t) * block_t, k_pad), (k_pad, n_pad)],
    )


# codesign's defaults: clip (128, 512, 256) on rows 16/32 x cols 8 (b_v 36/37
# for WS); full width: the (64, 4096, 14336) expert FFN on a 32x128 array
CASES = {
    "tasks_bv_le32_codesign": lambda: _tasks(16, 8, 24, tasks=2 * K.MAX_CALL_TASKS + 5, strips=512),
    "tasks_bv_gt32_codesign": lambda: _tasks(32, 8, 37, tasks=8192, strips=256),
    "strips_codesign": lambda: _strips(1024),
    "operand_stream_codesign": lambda: _operand_stream(512, 256),
    "per_gemm_ws_codesign": lambda: _per_gemm(128, 512, 256, 16, 8),
    "tasks_bv_le32_full_width": lambda: _tasks(32, 128, 32, tasks=14336, strips=128, t_seg1=65),
    "tasks_bv_gt32_full_width": lambda: _tasks(32, 128, 37, tasks=14336, strips=128, t_seg1=65),
    "strips_full_width": lambda: _strips(7200),
    "operand_stream_full_width": lambda: _operand_stream(4096, 14336),
    "per_gemm_ws_full_width": lambda: _per_gemm(64, 4096, 14336, 32, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Profiles find the profiling kernels by the HLO op name their jitted
# functions give the custom call (``%activity_profile_pallas_tasks.N =
# ... custom-call``), whatever name the Pallas kernel itself carries.
KERNEL_OPS = {
    "tasks_bv_le32_codesign": "activity_profile_pallas_tasks",
    "strips_codesign": "stream_strips_toggles_pallas",
}


@pytest.mark.parametrize("case", sorted(KERNEL_OPS))
def test_kernel_op_keeps_its_name(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    op = re.compile(rf"^%{KERNEL_OPS[case]}[.\d]* = .*custom-call")
    assert any(op.match(line.strip()) for line in text.splitlines())


def test_jop_program_is_named_after_its_core(monkeypatch):
    """The fused J/op program lowers as ``jit__coeff_eval_core`` (a bare
    ``functools.partial`` would lower as ``jit__unknown``)."""
    from repro.core.design_space import DesignSpace
    from repro.core.objective import evaluate_fleet_objective
    from repro.core.workloads import Gemm
    from repro.layout import power

    calls = []
    real = power._jitted_coeff_eval

    def spy(*key):
        fn = real(*key)

        def call(*args):
            calls.append((fn, args))
            return fn(*args)

        return call

    monkeypatch.setattr(power, "_jitted_coeff_eval", spy)
    grid = DesignSpace(
        rows=(8,), cols=(8, 16), input_bits=(8,), dataflows=("WS",), bus_invert=(False,)
    ).expand()
    evaluate_fleet_objective(grid, 0.2, 0.3, [Gemm("g", 16, 32, 16)], layouts=("uniform",),
                             use_jit=True)
    ((fn, args),) = calls
    assert fn.lower(*args).as_text().startswith("module @jit__coeff_eval_core")
