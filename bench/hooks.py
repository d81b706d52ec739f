"""What the harness records around the program, from the outside.

The program carries no spans, so the harness wraps the functions that mark
its layers, at the names their callers look up, and puts each call inside a
``jax.profiler.TraceAnnotation`` named ``bench.<layer>:<function>`` while a
run is traced. The annotations land on the profiler's clock beside the
device's events. Untraced runs call straight through.

Always recorded, traced or not (the comparison that decides ``correct``
needs them): the job set ``weighted_gemms`` returned, the profiling jobs
and their profiles, and the inputs ``evaluate_fleet_objective`` was given.
Recorded in traced runs only: the operand shapes of every activity-profile
kernel call, from which ``kernel_bytes`` counts the bytes the kernels must
move. Every backend compile is counted through
``jax.monitoring``.
"""

from __future__ import annotations

import contextlib
import importlib
import time

# (module the caller looks the name up in, attribute, layer)
SPANS = (
    ("repro.serving.codesign", "weighted_gemms", "expand"),
    ("repro.serving.codesign", "measured_design_gemm_activities", "profile"),
    ("repro.serving.codesign", "evaluate_fleet_objective", "price"),
    ("repro.core.objective", "lower_partition_coeffs", "lower"),
    ("repro.core.objective", "grid_coding_effective", "lower"),
    ("repro.layout.power", "lower_layout_coeffs", "lower"),
    ("repro.layout.power", "lower_coding_multipliers", "lower"),
)
PROFILE_BATCH = ("repro.core.pipeline", "run_profile_batch")
KERNELS = (
    ("repro.kernels.activity_profile.batch", "activity_profile_pallas_tasks"),
    ("repro.kernels.activity_profile.batch", "stream_strips_toggles_pallas"),
)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ACTIVE: "Hooks | None" = None
_LISTENING = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT and _ACTIVE is not None:
        _ACTIVE.compiles.append((time.perf_counter(), str(kwargs.get("fun_name")), duration))


class Hooks:
    """Wrappers over the program's layer entry points, for one run."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.current: dict = {}
        self.kernel_calls: list[dict] = []
        self.compiles: list[tuple[float, str, float]] = []
        self._saved: list[tuple[object, str, object]] = []

    def _patch(self, module: str, attr: str, make) -> None:
        mod = importlib.import_module(module)
        orig = getattr(mod, attr)
        self._saved.append((mod, attr, orig))
        setattr(mod, attr, make(orig))

    def _span(self, label: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(label)

    def install(self) -> "Hooks":
        global _ACTIVE, _LISTENING
        for module, attr, layer in SPANS:
            label = f"bench.{layer}:{module}.{attr}"

            def make(orig, label=label, attr=attr):
                def wrapper(*args, **kwargs):
                    with self._span(label):
                        out = orig(*args, **kwargs)
                    if attr == "weighted_gemms":
                        self.current["jobset"] = out
                    elif attr == "evaluate_fleet_objective":
                        self.current["pricing"] = (args, kwargs)
                    return out

                return wrapper

            self._patch(module, attr, make)

        def make_batch(orig):
            def wrapper(jobs, *args, **kwargs):
                jobs = list(jobs)
                out = orig(jobs, *args, **kwargs)
                self.current.setdefault("profile_batches", []).append((jobs, out[0]))
                return out

            return wrapper

        self._patch(*PROFILE_BATCH, make_batch)
        if self.trace:
            for module, attr in KERNELS:

                def make_kernel(orig, attr=attr):
                    def wrapper(strips, *args, **kwargs):
                        call = {
                            "kernel": attr,
                            "strips": tuple(strips.shape),
                            "itemsize": strips.dtype.itemsize,
                        }
                        if args:  # the task kernel: w_tiles, strip_ids, ...
                            call["w_tiles"] = tuple(args[0].shape)
                            call["tasks"] = int(args[1].shape[0])
                        self.kernel_calls.append(call)
                        return orig(strips, *args, **kwargs)

                    return wrapper

                self._patch(module, attr, make_kernel)
        if not _LISTENING:
            import jax

            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _LISTENING = True
        _ACTIVE = self
        return self

    def uninstall(self) -> None:
        global _ACTIVE
        for mod, attr, orig in reversed(self._saved):
            setattr(mod, attr, orig)
        self._saved.clear()
        if _ACTIVE is self:
            _ACTIVE = None

    def take(self) -> dict:
        """What the last answer recorded; starts a fresh record."""
        out, self.current = self.current, {}
        return out

    @contextlib.contextmanager
    def span(self, label: str):
        with self._span(label):
            yield
