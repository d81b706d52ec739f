"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals / window), averaged over the
cell's chips."""

from bench.trace import busy_ns


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    n = max(1, run.devices)
    busy = sum(busy_ns(run.trace, d) for d in range(n)) / n
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
