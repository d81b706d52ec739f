"""Host time per answer in activity profiling (operand synthesis, cache
lookups, the kernels and their collection): the harness's spans around
``measured_design_gemm_activities`` as ``codesign`` calls it."""

from bench.trace import covered


def read(run):
    if run.trace is None or not run.answers:
        return None
    spans = run.trace.spans_of("profile")
    if not spans:
        return None
    lo, hi = run.trace.window()
    return covered(spans, lo, hi) * 1e-6 / run.answers
