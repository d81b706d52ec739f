"""Profiler trace -> the events the metric readers use.

A traced run writes one ``.xplane.pb``. ``load`` keeps three things from it,
in nanoseconds on the profiler's clock:

- per device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
  line (each operation that ran) and of its ``XLA Modules`` line (each
  program run);
- the harness's own spans: host events named ``bench.*``.

``TraceView`` round-trips through JSON, so a small recorded trace can be
kept with the tests.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class TraceView:
    # device index -> [(start_ns, end_ns, name)]
    ops: dict[int, list[tuple[float, float, str]]]
    modules: dict[int, list[tuple[float, float, str]]]
    spans: list[tuple[float, float, str]]

    def to_json(self) -> dict:
        return {
            "ops": {str(k): v for k, v in self.ops.items()},
            "modules": {str(k): v for k, v in self.modules.items()},
            "spans": self.spans,
        }

    @classmethod
    def from_json(cls, doc: dict) -> "TraceView":
        def events(d):
            return {int(k): [tuple(e) for e in v] for k, v in d.items()}

        return cls(events(doc["ops"]), events(doc["modules"]), [tuple(s) for s in doc["spans"]])

    def window(self) -> tuple[float, float]:
        """The measured window on the trace's clock (the harness's span)."""
        wins = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError("the trace holds no window span")
        return wins[0]

    def spans_of(self, layer: str) -> list[tuple[float, float]]:
        prefix = f"bench.{layer}:"
        return [(s, e) for s, e, n in self.spans if n.startswith(prefix)]


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer would slow every call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir: str) -> TraceView:
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    ops: dict[int, list] = {}
    modules: dict[int, list] = {}
    spans: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dest.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events
                )
            else:
                spans.extend(
                    (e.start_ns, e.end_ns, e.name)
                    for e in line.events
                    if e.name.startswith("bench.")
                )
    spans.sort()
    return TraceView(ops, modules, spans)


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def busy_ns(view: TraceView, device: int) -> float:
    lo, hi = view.window()
    return covered(view.ops.get(device, []), lo, hi)


def idle_gaps(view: TraceView, device: int = 0) -> list[tuple[float, float]]:
    lo, hi = view.window()
    busy = merged(view.ops.get(device, []), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(view: TraceView, t: float) -> str:
    """The innermost harness span (other than the window) covering ``t``."""
    best = None
    for s, e, n in view.spans:
        if n != WINDOW_SPAN and s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "outside-spans"


def module_name(name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``: a program's name without its id."""
    return re.sub(r"\(\d+\)$", "", name)


def breakdown(view: TraceView, top: int = 10) -> dict:
    """The device programs that took most time in the window, summed over
    devices, and the longest idle gaps of device 0 with the harness span
    the host was in."""
    lo, hi = view.window()
    totals: dict[str, float] = {}
    for events in view.modules.values():
        for s, e, n in events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                totals[module_name(n)] = totals.get(module_name(n), 0.0) + d
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(view), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, d * 1e-9] for n, d in ops],
        "idle_gaps": [[label_at(view, 0.5 * (s + e)), (e - s) * 1e-9] for s, e in gaps],
    }
