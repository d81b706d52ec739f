"""The activity-profile Pallas kernels' share of the HBM peak: the bytes
they must move (``bench/kernel_bytes.py``, from the operand shapes of each
call in the window) over their summed device time times the HBM peak of
``bench/peaks.json``. A share of the memory bound only: the kernels are
integer vector work, and no vector-unit peak is sourced.

Device time is that of the kernels' own operations in the trace: the
``custom-call`` ops named after the kernels' jitted functions, not the
copies and reshapes around them.
"""

import re

from bench.kernel_bytes import total_bytes

KERNEL_OP = re.compile(
    r"^%?(activity_profile_pallas_tasks|stream_strips_toggles_pallas)[.\d]* = .*custom-call"
)


def read(run):
    if run.trace is None or run.peaks is None or not run.kernel_calls:
        return None
    lo, hi = run.trace.window()
    ns = sum(
        min(e, hi) - max(s, lo)
        for events in run.trace.ops.values()
        for s, e, n in events
        if e > lo and s < hi and KERNEL_OP.match(n)
    )
    if ns <= 0:
        return None
    return 100.0 * total_bytes(run.kernel_calls) / (ns * 1e-9 * run.peaks["hbm_bytes_per_s"])
