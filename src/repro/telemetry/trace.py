"""Engine spans on the profiler's clock, kept in a ring-buffer sink.

Two calls name a block of host work:

* ``Tracer.span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``
  of the same name (``args`` as its metadata) and, on leaving, appends
  ONE tuple to a bounded deque — the ring that ``chrome_trace()``
  exports.  The engines use it for what an operator reads back: each
  batch's dispatch, warm-up, swaps, flush-boundary scans.
* ``annotate(name, **args)`` opens the annotation only, and only while
  a profile is being taken; otherwise it returns a shared no-op.  The
  engines use it for the phases inside a batch (stage, put, record,
  fetch, route), which a profile resolves and the ring does not keep.

Either lands in the profiler's host plane on the device trace's clock
whenever a profile is taken.  With no profile active no annotation is
built: a ``span`` costs two ``perf_counter`` reads, one check of the
profiler's flag and one append; an ``annotate`` the check alone.  Spans
sit at dispatch-ring boundaries only — nothing inside compiled code — so
the depth-k overlap pipeline and every bit-identity contract stay
untouched.  A long-running engine keeps O(capacity) memory; old spans
fall off the back.

Export: ``chrome_trace()`` renders the ring as Chrome ``trace_event``
JSON (the ``{"traceEvents": [...]}`` object format) — complete events
(``"ph": "X"``) with microsecond timestamps relative to the tracer's
epoch, one ``tid`` lane per recording thread — loadable in
``chrome://tracing`` / Perfetto.
"""

from __future__ import annotations

import collections
import threading
import time

import jax.profiler

__all__ = ["Span", "Tracer", "Timed", "untraced", "annotate",
           "unannotated"]

_perf = time.perf_counter


class Span(collections.namedtuple(
        "Span", ["name", "cat", "t0", "dur_s", "tid", "args"])):
    """One recorded span: ``t0`` is seconds on the tracer's monotonic
    clock (``perf_counter`` minus the tracer epoch), ``dur_s`` its
    length, ``tid`` the recording thread's ident, ``args`` a small
    JSON-clean dict of annotations (batch ordinal, backend, rows, …)."""

    __slots__ = ()


class _Off:
    """The annotation opened while no profile is taken: nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


def annotate(name: str, **args):
    """A profiler annotation ``name`` (``args`` as its metadata) around a
    ``with`` block while a profile is being taken, a no-op otherwise.
    Nothing goes to a ring."""
    annotation = jax.profiler.TraceAnnotation
    if annotation.is_enabled():
        return annotation(name, **args)
    return _OFF


def unannotated(name: str, **args) -> _Off:
    """``annotate``'s signature, doing nothing."""
    return _OFF


class Timed:
    """Context manager that stamps ``t0``/``t1`` (``perf_counter``)
    around its body and records nothing: what an engine without a
    telemetry plane uses where it would open a span."""

    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = _perf()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _perf()
        return False


def untraced(name: str, *, cat: str = "serve", **args) -> Timed:
    """``Tracer.span``'s signature, timing only."""
    return Timed()


class _Open(Timed):
    """A span of ``Tracer.span``: an annotation while open, a ring entry
    on exit."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_tm")

    def __init__(self, tracer, name, cat, args):
        self._tracer, self._name, self._cat, self._args = \
            tracer, name, cat, args

    def __enter__(self):
        # an annotation records only while a profile is taken: skip
        # building one otherwise
        annotation = jax.profiler.TraceAnnotation
        if annotation.is_enabled():
            self._tm = annotation(self._name, **self._args)
            self._tm.__enter__()
        else:
            self._tm = None
        self.t0 = _perf()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self.t1 = _perf()
        if self._tm is not None:
            self._tm.__exit__(*exc)
        self._tracer._push((self._name, self._cat, self.t0, t1,
                            threading.get_ident(), self._args))
        return False


class Tracer:
    """Bounded span sink over the monotonic clock.

    ``span()`` opens a span around a block; ``record(name, t0, t1)``
    appends one from stamps the caller already holds, for a span that
    does not nest on one thread (an engine's batch lifetime, dispatch to
    fetch).  Recording takes no lock: deque.append is atomic under the
    GIL and the ring bound makes concurrent appends safe."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._spans: collections.deque[tuple] = collections.deque(
            maxlen=self.capacity
        )
        self.epoch = _perf()
        self.dropped = 0            # spans pushed out of the ring

    # ---------------------------------------------------------- recording

    def record(self, name: str, t0: float, t1: float, *,
               cat: str = "serve", args: dict | None = None) -> None:
        """Record a completed span from raw ``perf_counter`` stamps."""
        self._push((name, cat, t0, t1, threading.get_ident(), args or {}))

    def _push(self, entry: tuple) -> None:
        # raw stamps in a plain tuple: ``spans()`` builds the ``Span``s
        if len(self._spans) == self.capacity:
            self.dropped += 1
        self._spans.append(entry)

    def span(self, name: str, *, cat: str = "serve", **args) -> Timed:
        """A span around a ``with`` block: a profiler annotation named
        ``name`` with ``args`` as its metadata while the block runs, then
        one ring entry.  The returned object holds the block's ``t0`` and
        ``t1`` after it exits."""
        return _Open(self, name, cat, args)

    # ------------------------------------------------------------ reading

    def spans(self) -> list[Span]:
        """Snapshot copy of the ring, oldest first."""
        ep = self.epoch
        return [Span(name, cat, t0 - ep, max(0.0, t1 - t0), tid, args)
                for name, cat, t0, t1, tid, args in list(self._spans)]

    def __len__(self) -> int:
        return len(self._spans)

    def clear(self) -> None:
        self._spans.clear()
        self.dropped = 0

    def chrome_trace(self) -> dict:
        """The ring as Chrome ``trace_event`` JSON (object format).

        Complete events (``ph: "X"``), ``ts``/``dur`` in integer
        microseconds from the tracer epoch (monotonic, so events are
        well-ordered), ``pid`` fixed at 1 and ``tid`` a small stable
        int per recording thread.  Structure is what
        ``chrome://tracing`` / Perfetto load directly."""
        tids: dict[int, int] = {}
        events = []
        for s in self.spans():
            tid = tids.setdefault(s.tid, len(tids) + 1)
            events.append({
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": int(round(s.t0 * 1e6)),
                "dur": max(1, int(round(s.dur_s * 1e6))),
                "pid": 1,
                "tid": tid,
                "args": s.args,
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "repro.telemetry",
                "dropped_spans": self.dropped,
            },
        }
