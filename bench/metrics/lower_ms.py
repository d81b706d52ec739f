"""Host time per answer in coefficient and partition lowering: the union
of the harness's spans around ``lower_layout_coeffs``,
``lower_coding_multipliers``, ``lower_partition_coeffs`` and
``grid_coding_effective``, at the names their callers use."""

from bench.trace import covered


def read(run):
    if run.trace is None or not run.answers:
        return None
    spans = run.trace.spans_of("lower")
    if not spans:
        return None
    lo, hi = run.trace.window()
    return covered(spans, lo, hi) * 1e-6 / run.answers
