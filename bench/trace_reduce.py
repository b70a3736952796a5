"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace is read into plain planes, lines and events, so the reduction
can be checked on a constructed trace.  Device planes are those named
``/device:<platform>:<n>``.  On each, busy time is the union of the
operation intervals; the step time is the summed duration of the compiled
modules whose name matches a pattern; idle gaps are the holes in the busy
union inside the traced window, each labelled with the innermost host span
of the benchmark open over its middle, or else with the runtime's own
innermost host event there.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


@dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over device planes
    step_s: float                    # summed over device planes
    devices: int
    device_ops: list = field(default_factory=list)   # [[name, s]], top 10
    idle_gaps: list = field(default_factory=list)    # [[label, s]], top 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def load(path: str) -> list:
    """The trace's planes as :class:`Plane` objects."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [Plane(p.name, [Line(ln.name, [Event(e.name, e.start_ns,
                                                e.duration_ns)
                                          for e in ln.events])
                           for ln in p.lines])
            for p in data.planes]


def op_name(name: str) -> str:
    """An operation's instruction name without its HLO text:
    ``%while.11 = (u32[]...) while(...)`` becomes ``%while.11``."""
    return name.split(" = ", 1)[0]


def _is_device(plane: Plane) -> bool:
    return re.fullmatch(r"/device:[A-Z]+:\d+", plane.name) is not None


def _line(plane: Plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def host_events(planes) -> list:
    return [ev for p in planes if not _is_device(p)
            for ln in p.lines for ev in ln.events]


def _label(events, t: float) -> str:
    """The innermost benchmark span open at ``t``; else the innermost host
    event of the runtime, as ``host:<name>``; else ``host idle``."""
    open_ = [e for e in events if e.name != WINDOW_SPAN
             and e.start_ns <= t < e.end_ns]
    ours = [e for e in open_ if e.name.startswith(SPAN_PREFIX)]
    if ours:
        return min(ours, key=lambda e: e.dur_ns).name
    if open_:
        return "host:" + min(open_, key=lambda e: e.dur_ns).name
    return "host idle"


def reduce(planes, step_pattern: str) -> Summary:
    """Busy, step and idle numbers over the ``bench.window`` host span."""
    spans = host_events(planes)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    lo, hi = win[0].start_ns, win[0].end_ns
    step_re = re.compile(step_pattern)
    devices = [p for p in planes if _is_device(p)]
    busy = step = 0.0
    ops: dict[str, float] = {}
    gaps: list = []
    for dev in devices:
        op_line = _line(dev, OPS_LINE) or _line(dev, MODULES_LINE)
        evs = op_line.events if op_line is not None else []
        merged = _clip(_union((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy += sum(e - s for s, e in merged)
        for e in evs:
            if e.end_ns > lo and e.start_ns < hi:
                name = op_name(e.name)
                ops[name] = ops.get(name, 0.0) + e.dur_ns
        mods = _line(dev, MODULES_LINE)
        for e in (mods.events if mods is not None else []):
            if step_re.search(e.name) and e.end_ns > lo and e.start_ns < hi:
                step += min(e.end_ns, hi) - max(e.start_ns, lo)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    n = max(len(devices), 1)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   step_s=step / 1e9, devices=len(devices),
                   device_ops=[[k, v / 1e9] for k, v in top_ops],
                   idle_gaps=[[_label(spans, mid), g / 1e9]
                              for g, mid in gaps[:10]])
