"""Device time per answer of the fused J/op program: the summed duration,
over the cell's chips, of its program events in the window.

The program is ``jax.jit(functools.partial(_coeff_eval_core, ...))`` from
``repro.layout.power._jitted_coeff_eval``; JAX names a jitted partial
``jit__unknown``. Only events of that name that overlap the harness's
``price`` span (around ``evaluate_fleet_objective``) count, so another
jitted partial elsewhere in an answer is not taken for it.
"""

from bench.trace import covered, module_name

PROGRAMS = ("jit__unknown", "jit__coeff_eval_core")


def read(run):
    if run.trace is None or not run.answers:
        return None
    lo, hi = run.trace.window()
    price = run.trace.spans_of("price")
    total = 0.0
    for events in run.trace.modules.values():
        for s, e, n in events:
            if module_name(n) in PROGRAMS and covered(price, s, e) > 0:
                total += max(0.0, min(e, hi) - max(s, lo))
    if total <= 0:
        return None
    return total * 1e-6 / run.answers
