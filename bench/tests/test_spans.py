"""The engine's spans to host time per batch: on a small synthetic trace,
and through the readers on a profile of the engine taken on the CPU."""

import types

import numpy as np
import pytest

from bench import run, spans, spec, trace
from bench.trace import Event

READERS = ("stage_us", "route_us", "put_us", "fetch_us", "record_us")
US = 1000                              # ns


def _trace():
    # two batches on the engine thread: serve.put nested under
    # serve.dispatch, a JAX TraceMe beneath serve.put and one beneath
    # serve.dispatch itself (the launch), a name that still carries
    # TraceMe metadata, a bench span between batches; another thread's
    # serve.* span is not the engine's
    py = [Event("serve.stage#batch=0#", 0, 100 * US),
          Event("serve.dispatch", 100 * US, 300 * US),
          Event("serve.put", 110 * US, 150 * US),
          Event("DevicePutWithSharding", 120 * US, 100 * US),
          Event("PjitFunction(flow_serve_step)", 280 * US, 100 * US),
          Event("serve.record", 400 * US, 20 * US),
          Event("serve.fetch", 430 * US, 50 * US),
          Event("bench.collect", 480 * US, 10 * US),
          Event("serve.stage", 500 * US, 80 * US),
          Event("serve.dispatch", 580 * US, 200 * US),
          Event("serve.put", 590 * US, 100 * US)]
    host = {"python3": py,
            "tf_XLACpuClient": [Event("serve.stage", 0, 999 * US)]}
    return trace.Trace({}, host)


def test_self_time_excludes_nested_program_spans_only():
    table = spans.self_times(_trace())
    assert table == {
        "serve.stage": (180 * US, 2),
        # 300 - 150 and 200 - 100: the put is its child, the launch
        # (JAX's PjitFunction) its own time
        "serve.dispatch": (250 * US, 2),
        # DevicePutWithSharding is JAX's, so it stays serve.put's time
        "serve.put": (250 * US, 2),
        "serve.record": (20 * US, 1),
        "serve.fetch": (50 * US, 1),
    }
    # self times add up to the host time the top-level spans cover
    assert sum(ns for ns, _ in table.values()) == (100 + 300 + 20 + 50
                                                  + 80 + 200) * US


def test_per_batch_normalises_by_dispatch_spans(monkeypatch):
    table = spans.self_times(_trace())
    monkeypatch.setattr(spans, "for_run", lambda ctx: table)
    got = {name: spec.metric_reader(name)(None) for name in READERS}
    assert got == {"stage_us": 90.0, "route_us": None, "put_us": 125.0,
                   "fetch_us": 25.0, "record_us": 10.0}
    # a program without these spans (only the bench's own) reads nothing
    bare = trace.Trace({}, {"python3": [
        Event("engine.serve_stream", 0, 10 * US)]})
    assert spans.self_times(bare) == {}
    monkeypatch.setattr(spans, "for_run",
                        lambda ctx: spans.self_times(bare))
    assert all(spec.metric_reader(n)(None) is None for n in READERS)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_trace_reads_none(name, tmp_path, monkeypatch):
    read = spec.metric_reader(name)
    assert read(types.SimpleNamespace(reduced=None)) is None
    # a traced run whose profile left no file behind
    monkeypatch.setattr(run, "WORK_DIR", tmp_path)
    ctx = types.SimpleNamespace(reduced=object(),
                                cell=types.SimpleNamespace(name="x.y"))
    assert read(ctx) is None


def test_readers_on_a_profile_of_the_engine(tmp_path, monkeypatch):
    """The engine's spans reach the profiler's host plane under their
    names, one ``serve.dispatch`` per batch, and the readers read them."""
    import jax

    from repro.core import stageir
    from repro.flowstate import FlowStateSpec, StatefulPipeline
    from repro.serve import PacketServeEngine

    s = FlowStateSpec(n_slots=16, n_counters=1, n_ewma=1, hist_sizes=(3,),
                      ewma_alpha=0.5)
    pipe = StatefulPipeline([
        stageir.FlowKey((0,), s.n_slots),
        stageir.RegisterUpdate(s, ewma_cols=(1,), hist_cols=(1,),
                               hist_edges=(np.linspace(0, 1, 4)[1:-1],)),
        stageir.WindowStats(s, mode="all")])
    eng = PacketServeEngine(pipe, feature_dim=2, max_batch=8, depth=2)
    X = np.zeros((40, 2), np.float32)
    X[:, 0] = np.arange(40) % 6
    out = tmp_path / "trace" / "cell.x"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        eng.submit(X)
        assert len(eng.flush()) == 40
    finally:
        jax.profiler.stop_trace()

    table = spans.self_times(trace.load(trace.find_xplane(str(out))))
    assert table["serve.dispatch"][1] == 5          # 40 rows / 8
    for name in ("serve.stage", "serve.put", "serve.record",
                 "serve.fetch"):
        assert table[name][1] == 5, name
    assert table["serve.submit"][1] == 1
    monkeypatch.setattr(run, "WORK_DIR", tmp_path)
    ctx = types.SimpleNamespace(reduced=object(),
                                cell=types.SimpleNamespace(name="cell.x"))
    got = {n: spec.metric_reader(n)(ctx) for n in READERS}
    assert got.pop("route_us") is None              # one device: no routing
    assert all(v is not None and v > 0 for v in got.values()), got
