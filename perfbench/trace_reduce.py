"""From a profiler trace of the measured window to device numbers.

The JAX profiler writes an ``.xplane.pb``: one plane per device
(``/device:TPU:0`` …) whose ``XLA Ops`` line holds the operations that ran
on it, and a host plane (``/host:CPU``) whose threads hold the host spans
the benchmark opens with ``jax.profiler.TraceAnnotation`` (names starting
with ``bench.``).  :func:`load` turns the file into plain interval lists
and everything after it works on those lists, so the reduction is checked
on synthetic traces without a profiler.

* busy time of a device: the union of its operations' intervals, clipped
  to the window (``bench.window``);
* idle share: 1 − busy / window;
* idle gaps: the time of the window in which the busiest device runs
  nothing, split by the host phase (``bench.<phase>``) that covers it —
  what the host was doing while the chip waited.  The phases are spans
  that do not overlap; idle time under none of them is ``host``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
PHASE_PREFIX = "bench."
#: the chips themselves; the profiler adds planes for other agents
#: (``/device:CUSTOM:...``) that run no operation of the program
CHIP_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")

Interval = tuple[float, float]          # (start_s, end_s)


@dataclass
class Trace:
    """A trace as interval lists, in seconds on one clock."""

    #: device name → [(op name, start_s, end_s)]
    device_ops: dict[str, list[tuple[str, float, float]]]
    #: host spans the benchmark opened: [(name, start_s, end_s)]
    host_spans: list[tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> Interval | None:
        spans = [(s, e) for n, s, e in self.host_spans if n == WINDOW_SPAN]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax._src.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device_ops: dict[str, list[tuple[str, float, float]]] = {}
    host_spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if CHIP_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            evs = device_ops.setdefault(plane.name, [])
            for ln in ops:
                for ev in ln.events:
                    evs.append((op_name(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PHASE_PREFIX):
                        host_spans.append(
                            (ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return Trace(device_ops=device_ops, host_spans=host_spans)


def op_name(hlo: str) -> str:
    """An operation's name and result shape without the rest of its HLO
    text: ``%fusion.617 = f32[122880,8]{1,0:T(8,128)} fusion(..)`` →
    ``%fusion.617 f32[122880,8]`` (the shape tells which array a fusion
    writes; the name alone changes with every compile)."""
    name, _, rest = hlo.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])[:48]
    return f"{name} {shape}" if shape else name


def union(intervals: list[Interval], clip: Interval) -> list[Interval]:
    """Merge overlapping intervals, clipped to ``clip``."""
    lo, hi = clip
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(busy: list[Interval], a: float, b: float) -> float:
    """Seconds of merged ``busy`` inside ``[a, b)``."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in busy
               if s < b and e > a)


@dataclass
class Reduced:
    """What the metric readers and the result's ``breakdown`` read."""

    window_s: float
    busy_s: dict[str, float]                 # per device
    device_ops: list[tuple[str, float]]      # top ops, busiest device
    idle_gaps: list[tuple[str, float]]       # idle seconds by host phase

    @property
    def busiest(self) -> str:
        return max(self.busy_s, key=self.busy_s.get)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def idle_share(self, device: str) -> float:
        return 1.0 - self.busy_s[device] / self.window_s


def reduce(trace: Trace, top: int = 10) -> Reduced | None:
    """Reduce a trace to busy time, top operations and idle attribution.
    Returns ``None`` when the trace holds no window or no device."""
    win = trace.window()
    if win is None or not trace.device_ops:
        return None
    busy_iv = {d: union([(s, e) for _, s, e in ops], win)
               for d, ops in trace.device_ops.items()}
    busy_s = {d: sum(e - s for s, e in iv) for d, iv in busy_iv.items()}
    if not any(busy_s.values()):
        return None
    dev = max(busy_s, key=busy_s.get)
    per_op: dict[str, float] = {}
    for name, s, e in trace.device_ops[dev]:
        d = min(e, win[1]) - max(s, win[0])
        if d > 0:
            per_op[name] = per_op.get(name, 0.0) + d
    idle: dict[str, float] = {}
    for name, s, e in trace.host_spans:
        s, e = max(s, win[0]), min(e, win[1])
        if name == WINDOW_SPAN or e <= s:
            continue
        key = name[len(PHASE_PREFIX):]
        idle[key] = idle.get(key, 0.0) + (e - s) - overlap(busy_iv[dev], s, e)
    idle_total = (win[1] - win[0]) - busy_s[dev]
    idle["host"] = max(0.0, idle_total - sum(idle.values()))
    by_time = lambda kv: -kv[1]  # noqa: E731
    return Reduced(window_s=win[1] - win[0], busy_s=busy_s,
                   device_ops=sorted(per_op.items(), key=by_time)[:top],
                   idle_gaps=sorted(idle.items(), key=by_time)[:top])
