"""Bytes an activity-profile kernel call must move through HBM.

Counted from the operand shapes the harness recorded at the call: every
input array read once and the per-cell totals written once. A kernel that
reads a block more than once moves more than this, so the count is the
least traffic the call needs, and time x HBM peak can never fall below it.

- ``activity_profile_pallas_tasks``: the stacked strip windows
  (S, t_seg + 1, rows) and weight tiles (W, rows, cols), three int32
  metadata words per task (strip id, tile id, valid rows) and one int32
  total per task.
- ``stream_strips_toggles_pallas``: the strip windows (S, t_seg + 1, lanes)
  and one int32 total per strip.
"""

from __future__ import annotations

import math

INT32 = 4


def call_bytes(call: dict) -> int:
    strips = math.prod(call["strips"]) * call["itemsize"]
    if call["kernel"] == "activity_profile_pallas_tasks":
        tiles = math.prod(call["w_tiles"]) * call["itemsize"]
        return strips + tiles + 4 * INT32 * call["tasks"]
    if call["kernel"] == "stream_strips_toggles_pallas":
        return strips + INT32 * call["strips"][0]
    raise KeyError(f"no byte count for kernel {call['kernel']!r}")


def total_bytes(calls) -> int:
    return sum(call_bytes(c) for c in calls)
