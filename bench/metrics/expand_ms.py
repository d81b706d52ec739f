"""Host time per answer in expansion and traffic: the harness's spans
around ``weighted_gemms`` as ``codesign`` calls it."""

from bench.trace import covered


def read(run):
    if run.trace is None or not run.answers:
        return None
    spans = run.trace.spans_of("expand")
    if not spans:
        return None
    lo, hi = run.trace.window()
    return covered(spans, lo, hi) * 1e-6 / run.answers
