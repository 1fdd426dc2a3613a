"""The measured window: drive ``serve_stream`` with backlogged arrivals.

The generator hands the engine a chunk of the lap whenever it asks, until
the window closes, so the engine is never short of packets.  The rate is
the packets whose verdicts reached the host by the close, over the
window's seconds.

The engine's host work runs inside ``next()`` on ``serve_stream``; the
generator runs inside that call.  With a tracer the window starts and
stops the profiler at fixed offsets and marks its own host spans
(``bench.generate``, ``engine.serve_stream``, ``bench.collect``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

perf = time.perf_counter


@dataclasses.dataclass
class Tracer:
    """Profile ``[t0 + lead, t0 + lead + stretch]`` of the window."""

    out_dir: str
    lead: float
    stretch: float
    started: float | None = None
    stopped: float | None = None

    def start(self) -> None:
        """Profile devices and TraceMe host spans, not every Python call."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def tick(self, since_t0: float) -> None:
        if self.started is None and since_t0 >= self.lead:
            self.start()
            self.started = perf()
        elif (self.started is not None and self.stopped is None
              and since_t0 >= self.lead + self.stretch):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.started is not None and self.stopped is None:
            self.stopped = perf()
            jax.profiler.stop_trace()

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Result:
    t0: float
    seconds: float
    submitted: int                 # packets handed to the engine
    verdicts: np.ndarray           # every verdict, in arrival order
    recv: np.ndarray               # host time each verdict arrived
    stats_open: dict               # engine counters at the window's start
    stats_close: dict              # ... and at its close
    trace_stretch: tuple | None = None

    @property
    def answered_in_window(self) -> int:
        return int(np.sum(self.recv <= self.t0 + self.seconds))


def counters(eng) -> dict:
    s = eng.stats_
    return {"packets": s.packets, "batches": s.batches,
            "dispatch_s": s.dispatch_s,
            "backend_counts": dict(s.backend_counts)}


def run(eng, lap, start: int, seconds: float, chunk: int,
        tracer: Tracer | None = None) -> Result:
    """Serve replay rows ``[start, ...)`` for ``seconds``."""
    span = tracer.span if tracer is not None else (
        lambda _name: contextlib.nullcontext())
    state = {"submitted": 0}
    t0 = perf()
    t_end = t0 + seconds
    opened = counters(eng)
    closed: dict = {}

    def backlog():
        i = start
        while True:
            now = perf()
            if tracer is not None:
                tracer.tick(now - t0)
            if now >= t_end:
                closed.update(counters(eng))
                return
            with span("bench.generate"):
                rows = lap.take(i, chunk)
            i += chunk
            state["submitted"] += chunk
            yield rows

    outs, times = [], []
    stream = eng.serve_stream(backlog())
    while True:
        with span("engine.serve_stream"):
            v = next(stream, None)
        if v is None:
            break
        t = perf()
        with span("bench.collect"):
            outs.append(v)
            times.append(t)
    if tracer is not None:
        tracer.stop()
    verdicts = np.concatenate(outs) if outs else np.zeros(0, np.int32)
    recv = np.repeat(np.asarray(times), [len(v) for v in outs])
    stretch = None
    if tracer is not None and tracer.started is not None:
        stretch = (tracer.started, tracer.stopped)
    return Result(t0, seconds, state["submitted"], verdicts, recv, opened,
                  closed or counters(eng), stretch)
