"""The engine's own spans in the traced stretch -> host time per batch.

The serving engine opens a profiler annotation at each of its boundaries
(``serve.stage``, ``serve.route``, ``serve.dispatch`` holding
``serve.put``, ``serve.record``, ``serve.fetch``, ...;
``repro.telemetry.trace``), so they sit in the host plane of the
run's ``.xplane.pb`` on the device trace's clock.  ``self_times`` keeps
the engine thread's (``trace.HOST_THREAD``) ``serve.*`` events and gives,
per span name, the total self time and the count: a span's duration less
the part its nested ``serve.*`` spans cover, so JAX's own spans beneath a
program span (``DevicePutWithSharding``, ``PjitFunction(...)``) count as
that span's time and the names add up to the host time they cover.
``per_batch_us`` divides a name's self time by the ``serve.dispatch``
spans in the stretch.  A program that opens no such span (one older than
these names) reads ``None``.
"""

from __future__ import annotations

import collections
import functools
import os

from bench import trace

PREFIX = "serve."
BATCH = "serve.dispatch"           # one per batch dispatched


def span_name(name: str) -> str:
    """An annotation's name without TraceMe metadata (``name#k=v#``)."""
    return name.split("#", 1)[0]


def self_times(t: trace.Trace) -> dict:
    """``{name: (self ns, count)}`` of the engine thread's ``serve.*``
    spans."""
    self_ns: collections.Counter = collections.Counter()
    count: collections.Counter = collections.Counter()
    for line, events in t.host.items():
        if not trace.HOST_THREAD.match(line):
            continue
        spans = sorted(
            (trace.Event(span_name(e.name), e.start, e.dur) for e in events
             if e.name.startswith(PREFIX)),
            key=lambda e: (e.start, -e.dur))
        stack: list = []
        for ev in spans:
            while stack and stack[-1].end <= ev.start:
                stack.pop()
            if stack:                  # one thread: children never overlap
                self_ns[stack[-1].name] -= ev.dur
            self_ns[ev.name] += ev.dur
            count[ev.name] += 1
            stack.append(ev)
    return {k: (self_ns[k], count[k]) for k in count}


def per_batch_us(ctx, name: str) -> float | None:
    """Self time of span ``name`` per batch in the traced stretch, in µs;
    ``None`` with no trace, no batch or no such span."""
    table = for_run(ctx)
    if table is None or name not in table or BATCH not in table:
        return None
    return table[name][0] / table[BATCH][1] * 1e-3


def for_run(ctx) -> dict | None:
    """``self_times`` of the run's trace (``None`` where it has none)."""
    if ctx.reduced is None:
        return None
    from bench import run

    path = trace.find_xplane(str(run.WORK_DIR / "trace" / ctx.cell.name))
    if path is None:
        return None
    return _load(path, os.stat(path).st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> dict:
    """One load per trace file, shared by every reader of the run."""
    return self_times(trace.load(path))
