"""Profiler trace -> the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX (``ProfileData``): the device planes' op and module lines,
and the host threads' spans (the benchmark's own ``TraceAnnotation``
spans among them).  ``reduce`` turns that into, per device:

* busy time: the union of the intervals in which an op ran;
* each op's and each module's (jitted program's) total time and count,
  every op assigned to the module that was running at its midpoint;
* idle gaps: the stretches between busy intervals, each named by the
  innermost host span open at its midpoint (``idle`` where none is).

Everything is kept in integer nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_THREAD = re.compile(r"^python")     # the thread that runs the engine
_HLO = re.compile(r"^%?(\S+) = (\(?\w+\[[^\]]*\])")


def short_name(op: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's name and its (first) result shape:
    ``fusion.11 f32[1048576,28]``."""
    m = _HLO.match(op)
    if m is None:
        return op
    return f"{m.group(1)} {m.group(2).lstrip('(')}"


@dataclasses.dataclass
class Event:
    name: str
    start: int                 # ns
    dur: int                   # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict              # device name -> {"ops": [...], "modules": [...]}
    host: dict                 # host thread name -> [Event]


def find_xplane(out_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for key, line_name in (("ops", OP_LINE), ("modules", MODULE_LINE)):
                line = lines.get(line_name)
                if line is not None:
                    dev[key] = [Event(short_name(e.name), int(e.start_ns),
                                      int(e.duration_ns))
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name == HOST_PLANE:
            for name, line in lines.items():
                host[name] = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                              for e in line.events]
    return Trace(devices, host)


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events) -> int:
    return sum(e - s for s, e in merge((ev.start, ev.end) for ev in events))


def gaps(events, lo: int, hi: int) -> list:
    """Idle stretches inside ``[lo, hi]`` between the events' union."""
    out, cur = [], lo
    for s, e in merge((ev.start, ev.end) for ev in events):
        s, e = max(s, lo), min(e, hi)
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def innermost(spans, times) -> list:
    """Name of the innermost span open at each time (None where none is).

    ``spans`` come from one thread, so they nest; one sweep over spans
    and times, both sorted, keeps the stack of open spans."""
    spans = sorted(spans, key=lambda ev: (ev.start, -ev.dur))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out: list = [None] * len(times)
    stack: list = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(spans) and spans[j].start <= t:
            while stack and stack[-1].end <= spans[j].start:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out[i] = stack[-1].name if stack else None
    return out


@dataclasses.dataclass
class DeviceSummary:
    busy_ns: int
    op_ns: dict                # op name -> total ns
    op_count: dict
    module_ns: dict            # module name -> total ns
    module_count: dict
    module_op_ns: dict         # module -> {op name -> ns}


@dataclasses.dataclass
class Reduced:
    devices: dict              # device name -> DeviceSummary
    idle_gaps_ns: dict         # host span name -> idle ns (mean per device)

    def mean(self, fn) -> float | None:
        vals = [fn(d) for d in self.devices.values()]
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) if vals else None


def _module_at(mods, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].end >= t:
        return mods[i].name
    return None


def summarize_device(dev: dict) -> DeviceSummary:
    ops, mods = dev["ops"], sorted(dev["modules"], key=lambda e: e.start)
    starts = [m.start for m in mods]
    op_ns, op_count = collections.Counter(), collections.Counter()
    module_op_ns: dict = collections.defaultdict(collections.Counter)
    for ev in ops:
        op_ns[ev.name] += ev.dur
        op_count[ev.name] += 1
        mod = _module_at(mods, starts, ev.start + ev.dur // 2)
        module_op_ns[mod][ev.name] += ev.dur
    module_ns, module_count = collections.Counter(), collections.Counter()
    for m in mods:
        module_ns[m.name] += m.dur
        module_count[m.name] += 1
    return DeviceSummary(busy_ns(ops), dict(op_ns), dict(op_count),
                         dict(module_ns), dict(module_count),
                         {k: dict(v) for k, v in module_op_ns.items()})


def reduce(trace: Trace) -> Reduced:
    """Per-device summaries and idle gaps over the traced stretch.

    Gaps are taken between the first and the last op of any device and
    named by the innermost span open at their midpoint on the Python
    thread that runs the engine (``HOST_THREAD``)."""
    devs = {name: summarize_device(d) for name, d in trace.devices.items()}
    spans = [ev for name, evs in trace.host.items()
             if HOST_THREAD.match(name) for ev in evs]
    all_ops = [ev for d in trace.devices.values() for ev in d["ops"]]
    idle: collections.Counter = collections.Counter()
    if all_ops:
        lo = min(ev.start for ev in all_ops)
        hi = max(ev.end for ev in all_ops)
        for d in trace.devices.values():
            gs = gaps(d["ops"], lo, hi)
            names = innermost(spans, [(s + e) // 2 for s, e in gs])
            for (s, e), name in zip(gs, names):
                idle[name or "idle"] += e - s
        n = max(1, len(trace.devices))
        idle = collections.Counter({k: v / n for k, v in idle.items()})
    return Reduced(devs, dict(idle))


def top(counter: dict, n: int = 10, scale: float = 1e-9) -> list:
    """``[[name, seconds], ...]`` of the ``n`` largest entries."""
    items = sorted(counter.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * scale] for k, v in items]
