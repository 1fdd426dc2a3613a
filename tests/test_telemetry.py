"""Unified telemetry plane (docs/pipeline_ir.md#telemetry-contract), tier-1.

Covers the three surfaces — metrics registry, span tracer, event
journal — their exporters (Prometheus text, Chrome trace_event), the
engines' ``serve.*`` spans and the step's stable name in a profile,
the flow-table health scans, and the engine integration properties:
counter totals equal packets served under arbitrary interleavings with
hot swaps at depth > 1, bit-identical verdicts with telemetry on/off,
and the drift -> retrain -> swap -> mitigation event trail of a
coordinated-DDoS replay."""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import stageir
from repro.flowstate import (
    MITIGATED,
    DriftDetector,
    DriftSnapshot,
    FlowStateSpec,
    MitigationSpec,
    StatefulPipeline,
)
from repro.serve import HotSwapController, PacketServeEngine
from repro.serve.packet_engine import ServeStats
from repro.telemetry import (
    EVENT_KINDS,
    EventJournal,
    Telemetry,
    Tracer,
    batch_segmentation,
    mitigation_residency,
    table_health,
    to_prometheus,
)
from repro.telemetry.metrics import MetricsRegistry

HSET = settings(max_examples=10, deadline=None)


# ------------------------------------------------------------------ metrics


def test_counter_gauge_histogram_record_and_snapshot():
    m = MetricsRegistry()
    c = m.counter("pkts_total", "packets")
    c.default.inc(3)
    c.inc(2, backend="pallas")
    g = m.gauge("occ", "occupancy")
    g.default.set(0.5)
    h = m.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.default.observe(v)

    snap = m.snapshot()
    assert snap["pkts_total"]["kind"] == "counter"
    vals = {tuple(v["labels"].items()): v["value"]
            for v in snap["pkts_total"]["values"]}
    assert vals[()] == 3.0
    assert vals[(("backend", "pallas"),)] == 2.0
    assert snap["occ"]["values"][0]["value"] == 0.5
    hv = snap["lat_ms"]["values"][0]
    assert [b["count"] for b in hv["buckets"]] == [1, 1, 1]
    assert hv["buckets"][-1]["le"] == float("inf")
    assert hv["count"] == 3 and hv["sum"] == 55.5
    # snapshot is a copy: later recording never mutates it
    c.default.inc(100)
    assert vals[()] == 3.0


def test_registry_get_or_create_and_kind_mismatch():
    m = MetricsRegistry()
    assert m.counter("x") is m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    assert m.get("x").kind == "counter"
    assert m.get("missing") is None


def test_label_children_are_interned_handles():
    m = MetricsRegistry()
    c = m.counter("y")
    assert c.labels(backend="pallas") is c.labels(backend="pallas")
    assert c.labels(backend="pallas") is not c.labels(backend="interpret")


# ------------------------------------------------------------------- tracer


def test_tracer_ring_bound_and_chrome_trace_structure():
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.record(f"s{i}", float(i), float(i) + 0.001, args={"i": i})
    assert len(tr) == 4 and tr.dropped == 2
    assert [s.name for s in tr.spans()] == ["s2", "s3", "s4", "s5"]

    ct = tr.chrome_trace()
    assert set(ct) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert ct["otherData"]["dropped_spans"] == 2
    assert len(ct["traceEvents"]) == 4
    for ev in ct["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int)
        assert ev["dur"] >= 1 and ev["pid"] == 1 and ev["tid"] >= 1
        assert isinstance(ev["name"], str) and isinstance(ev["cat"], str)
    # ts are monotonic (single-threaded recording) and JSON-clean
    ts = [e["ts"] for e in ct["traceEvents"]]
    assert ts == sorted(ts)
    json.dumps(ct)


def test_tracer_span_contextmanager_records_args():
    tr = Tracer()
    with tr.span("compile", cat="warm", backend="pallas"):
        pass
    (s,) = tr.spans()
    assert s.name == "compile" and s.cat == "warm"
    assert s.args == {"backend": "pallas"} and s.dur_s >= 0.0


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` while a profile is
    taken: logs each annotation's name and metadata as it opens and
    closes."""

    def __init__(self):
        self.log = []

    @staticmethod
    def is_enabled():
        return True

    def __call__(self, name, **kwargs):
        log = self.log

        class Annotation:
            def __enter__(self):
                log.append(("open", name, kwargs))
                return self

            def __exit__(self, *exc):
                log.append(("close", name, kwargs))
                return False

        return Annotation()


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_tracer_span_opens_profiler_annotation_and_fills_ring(annotations):
    tr = Tracer()
    with tr.span("serve.stage", batch=3) as sp:
        # the annotation is open while the block runs; the ring is not
        # written until the span closes
        assert annotations.log == [("open", "serve.stage", {"batch": 3})]
        assert len(tr) == 0
    assert annotations.log[-1] == ("close", "serve.stage", {"batch": 3})
    (s,) = tr.spans()
    assert s.name == "serve.stage" and s.cat == "serve"
    assert s.args == {"batch": 3}
    assert s.dur_s == pytest.approx(sp.t1 - sp.t0)


def test_telemetry_false_opens_no_annotation(annotations):
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                            feature_dim=2, max_batch=8, telemetry=False)
    eng.submit(_flow_packets(np.random.default_rng(4), 20))
    assert len(eng.flush()) == 20
    # the engine opens none of its own; the pipeline's dispatch names its
    # put in any profile, whoever calls it
    assert {name for _, name, _ in annotations.log} == {"serve.put"}
    assert eng.stats_.dispatch_s > 0.0      # still timed, just not traced


def test_annotate_is_a_shared_no_op_without_a_profile():
    import jax.profiler

    from repro.telemetry.trace import annotate

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert annotate("serve.stage", batch=1) is annotate("serve.fetch")
    with annotate("serve.stage", batch=1) as a:
        assert a is not None


# ------------------------------------------------------------------ journal


def test_journal_orders_events_and_round_trips_file(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = EventJournal(path)
    j.emit("drift", score=3.2)
    j.emit("hot_swap", lat_ms=1.5, pkt_offset=1024)
    j.emit("slo_gate", ok=True)
    j.close()

    evs = j.events()
    assert [e["seq"] for e in evs] == [0, 1, 2]
    ts = [e["t_s"] for e in evs]
    assert ts == sorted(ts)
    assert j.kinds() == {"drift", "hot_swap", "slo_gate"}
    assert [e["kind"] for e in j.events("drift")] == ["drift"]

    loaded = EventJournal.load(path)
    assert loaded == evs
    # dump() writes the same JSON-lines form
    assert EventJournal.load(j.dump(str(tmp_path / "d.jsonl"))) == evs


def test_journal_ring_is_bounded():
    j = EventJournal(capacity=8)
    for i in range(20):
        j.emit("drift", i=i)
    evs = j.events()
    assert len(evs) == 8 and evs[0]["i"] == 12 and evs[-1]["seq"] == 19


def test_event_kinds_vocabulary_is_stable():
    assert set(EVENT_KINDS) == {
        "drift", "retrain_start", "retrain_done", "hot_swap",
        "mitigation_engage", "mitigation_release", "backend_fallback",
        "slo_gate",
    }


# ---------------------------------------------------------------- exporters


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("pkts_total", "packets served").inc(5, backend="pallas")
    m.gauge("occ").default.set(0.25)
    h = m.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.default.observe(0.5)
    h.default.observe(5.0)
    text = to_prometheus(m.snapshot())
    assert "# HELP pkts_total packets served" in text
    assert "# TYPE pkts_total counter" in text
    assert 'pkts_total{backend="pallas"} 5' in text
    assert "occ 0.25" in text
    # histogram buckets are CUMULATIVE, +Inf closes the family
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_sum 5.5" in text
    assert "lat_ms_count 2" in text


def test_prometheus_escapes_label_values():
    m = MetricsRegistry()
    m.counter("c").inc(1, path='a"b\\c')
    assert 'c{path="a\\"b\\\\c"} 1' in to_prometheus(m.snapshot())


# -------------------------------------------------------------- flow health


def _spec(n_slots=16):
    return FlowStateSpec(n_slots=n_slots, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)


def _flow_stages(spec):
    fk = stageir.FlowKey((0,), spec.n_slots)
    ru = stageir.RegisterUpdate(
        spec, ewma_cols=(1,), hist_cols=(1,),
        hist_edges=(np.linspace(0, 1, 4)[1:-1],),
    )
    return [fk, ru, stageir.WindowStats(spec, mode="all")]


class _FakeState:
    def __init__(self, keys):
        self.keys = np.asarray(keys, np.int32)


def test_table_health_counts_inserts_and_evictions():
    prev = np.array([-1, 5, 7, -1], np.int32)
    cur = np.array([3, 5, 9, -1], np.int32)
    h = table_health(_FakeState(cur), prev)
    assert h["slots"] == 4 and h["occupied"] == 3
    assert h["occupancy_frac"] == 0.75
    assert h["inserts"] == 1          # slot 0: empty -> 3
    assert h["evictions"] == 1        # slot 2: 7 -> 9 while occupied
    np.testing.assert_array_equal(h["keys"], cur)
    assert h["mit_slots"] == 0        # no action table


def test_mitigation_residency_counts_marked_flows():
    class S:
        mit_spec = MitigationSpec(n_slots=4, mode="drop", threshold=2)
        mit_keys = np.array([1, -1, 3, 4], np.int32)
        mit_regs = np.array([[3, 0], [9, 0], [1, 0], [2, 0]], np.float32)

    r = mitigation_residency(S())
    assert r == {"mit_slots": 4, "mit_occupied": 3, "mit_marked": 2}


def test_batch_segmentation_matches_kernel_rank_semantics():
    # chain depths: slot 3 x4, slot 5 x2, slot 9 x1
    slots = np.array([3, 5, 3, 9, 3, 5, 3])
    seg = batch_segmentation(slots, par_rounds=2)
    assert seg["n_live"] == 7
    assert seg["max_chain"] == 4
    assert seg["n_deep"] == 2         # ranks 2 and 3 of the slot-3 chain
    assert seg["drain_heavy"] is (2 * 8 > 7 * 7)
    assert batch_segmentation(np.array([]), par_rounds=2) == {
        "n_live": 0, "n_deep": 0, "max_chain": 0, "drain_heavy": False}
    # a deep single chain: 30/32 deep strictly exceeds 7/8 -> drain-heavy
    assert batch_segmentation(np.full(32, 7), par_rounds=2)[
        "drain_heavy"] is True
    # ...but exactly 7/8 deep does not (the flag's rule is strict)
    assert batch_segmentation(np.full(16, 7), par_rounds=2)[
        "drain_heavy"] is False


def test_batch_segmentation_default_par_rounds_is_kernel_constant():
    from repro.kernels.flow_update.kernel import PAR_ROUNDS

    slots = np.full(PAR_ROUNDS + 3, 1)
    assert batch_segmentation(slots)["n_deep"] == 3


# --------------------------------------------------- ServeStats (satellite)


def test_empty_serve_stats_round_trips_json_clean():
    """Regression: an engine that served nothing must report 0.0 (not
    nan) latency percentiles, and as_dict() must round-trip JSON."""
    s = ServeStats()
    d = s.as_dict()
    assert d["lat_p50_ms"] == 0.0
    assert d["lat_p95_ms"] == 0.0
    assert d["lat_p99_ms"] == 0.0
    assert d["pkt_per_s"] == 0.0
    assert json.loads(json.dumps(d)) == d
    # and a freshly constructed engine (warm-up only) is equally clean
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                            feature_dim=2, max_batch=8)
    d = eng.stats()
    assert d["lat_p50_ms"] == 0.0 and d["packets"] == 0
    assert json.loads(json.dumps(d)) == d


# -------------------------------------------------------- engine integration


def _flow_packets(rng, n, flows=6):
    X = np.zeros((n, 2), np.float32)
    X[:, 0] = rng.integers(0, flows, n)
    X[:, 1] = rng.random(n)
    return X


def test_engine_counters_spans_and_prometheus_end_to_end():
    rng = np.random.default_rng(0)
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                            feature_dim=2, max_batch=8, depth=2)
    eng.TELEMETRY_SEG_SAMPLE = 1      # exact schedule counts for the test
    tel = eng.telemetry()
    assert tel is not None
    X = _flow_packets(rng, 100)
    eng.submit(X)
    eng.flush()

    snap = tel.snapshot()
    one = {k: snap[k]["values"][0]["value"] for k in snap
           if snap[k]["kind"] in ("counter", "gauge")}
    assert one["serve_packets_total"] == 100
    assert one["serve_batches_total"] == 13   # ceil(100 / 8)
    assert one["serve_pad_packets_total"] == 13 * 8 - 100
    assert one["serve_depth"] == 2
    # every batch classified lockstep-or-drain when sampling is off
    assert (one["flow_lockstep_batches_total"]
            + one["flow_drain_batches_total"]) == 13
    # flush-boundary health scan ran against the live table
    assert one["flow_occupied_slots"] == eng.state.occupied
    # per-backend labelled counter carries the engine's actual backend
    bb = snap["serve_backend_batches_total"]["values"]
    assert {v["labels"]["backend"]: v["value"] for v in bb} == {
        eng.backend: 13}
    # histograms observed one value per batch
    assert snap["serve_dispatch_ms"]["values"][0]["count"] == 13
    assert snap["serve_batch_latency_ms"]["values"][0]["count"] == 13
    # exporters render the live registry
    assert "serve_packets_total 100" in tel.prometheus()
    # the trace has warm-up + dispatch + batch spans, Chrome-valid
    names = {s.name for s in tel.tracer.spans()}
    assert {"serve.warm_up", "serve.dispatch", "serve.batch"} <= names
    for ev in tel.chrome_trace()["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 1


def test_one_stateful_batch_emits_the_serve_spans_in_order(annotations):
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                            feature_dim=2, max_batch=8, depth=2)
    tel = eng.telemetry()
    tel.tracer.clear()
    del annotations.log[:]
    eng.submit(_flow_packets(np.random.default_rng(5), 8))
    assert len(eng.flush()) == 8

    # a profile sees every phase, nested as opened and closed: the put
    # inside the dispatch, the rest of the dispatch is the launch
    assert [(ev, name) for ev, name, _ in annotations.log] == [
        ("open", "serve.submit"), ("close", "serve.submit"),
        ("open", "serve.stage"), ("close", "serve.stage"),
        ("open", "serve.dispatch"),
        ("open", "serve.put"), ("close", "serve.put"),
        ("close", "serve.dispatch"),
        ("open", "serve.record"), ("close", "serve.record"),
        ("open", "serve.fetch"), ("close", "serve.fetch"),
        ("open", "serve.health_scan"), ("close", "serve.health_scan")]
    # the engine's phases of one batch share its ordinal
    assert {kw["batch"] for _, name, kw in annotations.log if name in (
        "serve.stage", "serve.dispatch", "serve.record",
        "serve.fetch")} == {0}
    # the ring keeps what an operator reads back: the dispatch (the
    # interval dispatch_s sums), the batch's lifetime, the scan
    spans = tel.tracer.spans()
    assert [s.name for s in spans] == [
        "serve.dispatch", "serve.batch", "serve.health_scan"]
    assert spans[0].args == {"batch": 0}
    assert spans[0].dur_s == pytest.approx(eng.stats_.dispatch_s)
    assert spans[1].args["batch"] == 0 and spans[1].args["rows"] == 8


_ROUTE_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    import jax.profiler
    from repro.core import stageir
    from repro.flowstate import FlowStateSpec, StatefulPipeline
    from repro.serve import ShardedPacketServeEngine

    assert len(jax.devices()) == 4
    spec = FlowStateSpec(n_slots=32, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)
    fk = stageir.FlowKey((0,), spec.n_slots)
    ru = stageir.RegisterUpdate(spec, ewma_cols=(1,), hist_cols=(1,),
                                hist_edges=(np.linspace(0, 1, 4)[1:-1],))
    pipe = StatefulPipeline([fk, ru, stageir.WindowStats(spec, mode="all")])
    eng = ShardedPacketServeEngine(pipe, feature_dim=2, max_batch=32,
                                   depth=2)
    assert eng.sharded and eng.n_shards == 4
    log = []

    # stands in for the profiler's annotation while a profile is taken:
    # logs each name and its metadata as it opens
    class Annotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            log.append((self.name, self.kwargs))
            return self

        def __exit__(self, *exc):
            return False

    jax.profiler.TraceAnnotation = Annotation
    X = np.zeros((64, 2), np.float32)
    X[:, 0] = np.random.default_rng(0).integers(0, 40, 64)
    eng.submit(X)
    assert len(eng.flush()) == 64
    names = [name for name, _ in log]
    n = names.count("serve.dispatch")
    assert n >= 2 and names.count("serve.route") == n
    assert names.count("serve.put") == n
    assert names.count("serve.stage") == 2 * n
    assert names.count("serve.record") == names.count("serve.fetch") == n
    for k in range(n):
        assert {name for name, kw in log if kw.get("batch") == k} >= {
            "serve.stage", "serve.route", "serve.dispatch",
            "serve.record", "serve.fetch"}
    print("ROUTE-OK")
""")


def test_sharded_engine_emits_route_span_on_four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _ROUTE_SCRIPT], capture_output=True,
        text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ROUTE-OK" in proc.stdout


@pytest.mark.parametrize("sharded", [False, True])
def test_step_module_has_a_stable_name(sharded):
    """The jitted step is ``flow_serve_step`` on one device and under
    shard_map (here a 1-ary mesh), so a profile's module line names it
    ``jit_flow_serve_step`` whatever the backend lowered."""
    import jax.numpy as jnp

    from repro.serve import ShardedPacketServeEngine

    pipe = StatefulPipeline(_flow_stages(_spec()))
    x, v = jnp.zeros((8, 2), jnp.float32), jnp.zeros((8,), jnp.int32)
    if sharded:
        eng = ShardedPacketServeEngine(pipe, feature_dim=2, max_batch=8,
                                       min_shards=1, telemetry=False)
        lowered = eng._sharded_fn.lower(*eng.state.arrays(), x[None],
                                        v[None])
    else:
        lowered = pipe._step.lower(*pipe._state_arrays(pipe.init_state()),
                                   x, v)
    assert "module @jit_flow_serve_step" in lowered.as_text()


def test_telemetry_false_disables_recording_and_keeps_verdicts():
    rng = np.random.default_rng(1)
    X = _flow_packets(rng, 60)
    eng_off = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                                feature_dim=2, max_batch=8,
                                telemetry=False)
    eng_on = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                               feature_dim=2, max_batch=8)
    assert eng_off.telemetry() is None
    eng_off.submit(X)
    eng_on.submit(X)
    np.testing.assert_array_equal(eng_off.flush(), eng_on.flush())


def test_shared_plane_aggregates_across_engines():
    tel = Telemetry()
    rng = np.random.default_rng(2)
    X = _flow_packets(rng, 40)
    for _ in range(2):
        eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                                feature_dim=2, max_batch=8, telemetry=tel)
        eng.submit(X)
        eng.flush()
    snap = tel.snapshot()
    assert snap["serve_packets_total"]["values"][0]["value"] == 80


def test_mitigated_verdicts_are_counted():
    spec = _spec(n_slots=64)
    stages = _flow_stages(spec)
    rng = np.random.default_rng(7)
    n_in = stages[2].n_out
    w1 = rng.normal(size=(n_in, 6)).astype(np.float32)
    w2 = rng.normal(size=(6, 2)).astype(np.float32)
    mlp = stageir.FusedMLP([w1, w2], [np.zeros(6, np.float32),
                                      np.zeros(2, np.float32)])
    pipe = StatefulPipeline(
        stages + [mlp, stageir.Reduce("argmax"),
                  stageir.Mitigate(MitigationSpec(
                      n_slots=64, mode="drop", threshold=2))])
    eng = PacketServeEngine(pipe, feature_dim=2, max_batch=16)
    X = _flow_packets(np.random.default_rng(3), 400, flows=4)
    eng.submit(X)
    v = eng.flush()
    dropped = int((v == MITIGATED).sum())
    snap = eng.telemetry().snapshot()
    assert snap["serve_mitigated_packets_total"]["values"][0]["value"] \
        == dropped
    if dropped:   # engage event journaled at the flush-boundary scan
        assert "mitigation_engage" in eng.telemetry().journal.kinds()
        assert snap["flow_mit_marked"]["values"][0]["value"] > 0


def test_requested_pallas_fallback_is_journaled(monkeypatch):
    from repro.core import pallas_backend

    monkeypatch.setattr(pallas_backend, "pallas_available", lambda: False)
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(_spec())),
                            feature_dim=2, max_batch=8, backend="pallas")
    evs = eng.telemetry().journal.events("backend_fallback")
    assert evs and evs[0]["requested"] == "pallas"
    assert evs[0]["actual"] == eng.backend


# ------------------------------------------- swap-concurrency property


@given(data=st.data())
@HSET
def test_counters_account_for_every_packet_across_swaps(data):
    """Satellite property: under arbitrary submit/flush/swap
    interleavings at depth > 1 — with the swap parked from a SEPARATE
    thread, racing the serving loop — the packet counter equals the
    packets submitted, batches equal lockstep+drain classifications, and
    the journal records exactly the installed swaps."""
    spec = _spec()
    eng = PacketServeEngine(StatefulPipeline(_flow_stages(spec)),
                            feature_dim=2, max_batch=8,
                            depth=data.draw(st.integers(2, 4)))
    eng.TELEMETRY_SEG_SAMPLE = 1
    rng = np.random.default_rng(data.draw(st.integers(0, 1000)))
    n_ops = data.draw(st.integers(1, 6))
    swap_at = data.draw(st.integers(0, n_ops - 1))
    total = 0
    for i in range(n_ops):
        if i == swap_at:
            t = threading.Thread(target=eng.swap, args=(
                StatefulPipeline(_flow_stages(spec)),))
            t.start()
            t.join()
        n = data.draw(st.integers(1, 40))
        eng.submit(_flow_packets(rng, n))
        total += n
        if data.draw(st.booleans()):
            eng.flush()
    assert len(eng.flush()) >= 0
    while eng.swap_pending:           # force the parked swap in
        eng.flush()

    snap = eng.telemetry().snapshot()
    one = {k: snap[k]["values"][0]["value"] for k in snap
           if snap[k]["kind"] == "counter"}
    assert one["serve_packets_total"] == total
    assert one["serve_packets_total"] + one["serve_pad_packets_total"] \
        == one["serve_batches_total"] * 8
    assert (one["flow_lockstep_batches_total"]
            + one["flow_drain_batches_total"]) \
        == one["serve_batches_total"]
    assert one["serve_swaps_total"] == eng.stats_.swaps == 1
    swaps = eng.telemetry().journal.events("hot_swap")
    assert len(swaps) == 1 and swaps[0]["pkt_offset"] <= total


# --------------------------------------- closed-loop replay event trail


def test_coordinated_ddos_replay_event_trail():
    """Acceptance: replaying coordinated_ddos against a drift-armed,
    mitigated engine journals drift, hot_swap and mitigation events with
    monotonic timestamps, and the Chrome trace validates structurally."""
    from repro.data import traffic

    spec = FlowStateSpec(n_slots=256, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)
    fk = stageir.FlowKey((0, 3), spec.n_slots)
    ru = stageir.RegisterUpdate(
        spec, ewma_cols=(2,), hist_cols=(1,),
        hist_edges=(np.array([64.0, 512.0], np.float32),),
    )
    ws = stageir.WindowStats(spec, mode="all")

    def make_pipe():
        rng = np.random.default_rng(5)
        n_in = ws.n_out
        w1 = rng.normal(size=(n_in, 4)).astype(np.float32)
        w2 = rng.normal(size=(4, 2)).astype(np.float32)
        mlp = stageir.FusedMLP([w1, w2], [np.zeros(4, np.float32),
                                          np.zeros(2, np.float32)])
        return StatefulPipeline(
            [fk, ru, ws, mlp, stageir.Reduce("argmax"),
             stageir.Mitigate(MitigationSpec(
                 n_slots=256, mode="drop", threshold=2))])

    stream = traffic.make_stream("coordinated_ddos", n_packets=2000,
                                 seed=3)
    X = stream.packets
    eng = PacketServeEngine(make_pipe(),
                            feature_dim=len(traffic.COLUMNS),
                            max_batch=64, depth=2)
    snap0 = DriftSnapshot.from_packets(X[:256], cols=(1, 2), window=64)
    ctrl = HotSwapController(
        eng, DriftDetector(snap0, threshold=1e-6, patience=1),
        lambda windows: make_pipe(), buffer_windows=4)

    for i in range(0, len(X), 128):
        w = X[i:i + 128]
        ctrl.observe(w)
        eng.submit(w)
        eng.flush()
    assert ctrl.wait(30)
    eng.flush()                       # install the parked swap

    tel = eng.telemetry()
    kinds = tel.journal.kinds()
    assert {"drift", "retrain_start", "retrain_done", "hot_swap"} <= kinds
    assert "mitigation_engage" in kinds, (
        "coordinated_ddos replay must engage the action table")
    evs = tel.journal.events()
    ts = [e["t_s"] for e in evs]
    assert ts == sorted(ts) and [e["seq"] for e in evs] == list(
        range(len(evs)))
    # the trail is causally ordered: drift before retrain before swap
    first = {k: next(e["seq"] for e in evs if e["kind"] == k)
             for k in ("drift", "retrain_start", "hot_swap")}
    assert first["drift"] < first["retrain_start"] < first["hot_swap"]
    # Chrome trace validates structurally and serializes
    ct = tel.chrome_trace()
    assert {"serve.warm_up", "serve.dispatch", "serve.batch",
            "serve.swap_install"} <= {
        e["name"] for e in ct["traceEvents"]}
    json.dumps(ct)
