"""PacketServeEngine: arrival-order preservation under arbitrary
submit/flush interleavings, latency percentiles, stateful serving (tier-1).

The ordering property is the engine's core contract: whatever mix of
ragged ``submit`` chunks, intermediate ``flush`` calls and
``serve_stream`` pulls, verdicts come back in arrival order and — on the
stateful path — the register file sees packets in exactly that order."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pallas_backend, stageir
from repro.data import traffic
from repro.flowstate import FlowStateSpec, StatefulPipeline
from repro.serve.packet_engine import PacketServeEngine, ServeStats

HSET = settings(max_examples=12, deadline=None)


def _tag_pipeline(x):
    """Verdict = the packet's own tag column: order-revealing."""
    return x[:, 0].astype(np.int32)


def _tagged(n, start=0):
    out = np.zeros((n, 2), np.float32)
    out[:, 0] = np.arange(start, start + n)
    return out


# ------------------------------------------------------ ordering property


@given(data=st.data())
@HSET
def test_submit_flush_interleavings_preserve_arrival_order(data):
    eng = PacketServeEngine(_tag_pipeline, feature_dim=2,
                            max_batch=data.draw(st.integers(1, 13)),
                            depth=data.draw(st.integers(1, 4)))
    total, got = 0, []
    for _ in range(data.draw(st.integers(1, 12))):
        if data.draw(st.booleans()) or total == 0:
            n = data.draw(st.integers(1, 37))
            eng.submit(_tagged(n, start=total))
            total += n
        else:
            got.append(eng.flush())
    got.append(eng.flush())
    verdicts = np.concatenate([g for g in got if len(g)])
    np.testing.assert_array_equal(verdicts, np.arange(total))
    assert eng.pending == 0
    assert eng.in_flight == 0


@given(data=st.data())
@HSET
def test_async_depth_preserves_order_on_jitted_pipeline(data):
    """depth>1 keeps device-array results in flight (lazy fetch); order
    must survive arbitrary submit/flush interleavings on a REAL jitted
    pipeline, where outputs are async device handles, not numpy."""
    import jax

    jitted = jax.jit(lambda x: x[:, 0].astype("int32"))
    eng = PacketServeEngine(jitted, feature_dim=2,
                            max_batch=data.draw(st.integers(2, 17)),
                            depth=data.draw(st.integers(2, 4)))
    total, got = 0, []
    for _ in range(data.draw(st.integers(1, 8))):
        n = data.draw(st.integers(1, 53))
        eng.submit(_tagged(n, start=total))
        total += n
        if data.draw(st.booleans()):
            got.append(eng.flush())
    got.append(eng.flush())
    verdicts = np.concatenate([g for g in got if len(g)])
    np.testing.assert_array_equal(verdicts, np.arange(total))


@given(data=st.data())
@HSET
def test_serve_stream_ragged_chunks_preserve_order(data):
    sizes = data.draw(st.lists(st.integers(1, 41), min_size=1, max_size=8))
    eng = PacketServeEngine(_tag_pipeline, feature_dim=2,
                            max_batch=data.draw(st.integers(2, 17)))
    chunks, total = [], 0
    for n in sizes:
        chunks.append(_tagged(n, start=total))
        total += n
    got = np.concatenate(list(eng.serve_stream(iter(chunks))))
    np.testing.assert_array_equal(got, np.arange(total))


# ------------------------------------------------------------ percentiles


def test_latency_percentiles_in_stats():
    stats = ServeStats()
    assert stats.lat_p50_ms == 0.0 and stats.lat_p95_ms == 0.0
    assert stats.lat_p99_ms == 0.0
    eng = PacketServeEngine(_tag_pipeline, feature_dim=2, max_batch=8)
    assert eng.stats()["lat_p50_ms"] == 0.0    # warm-up batch not counted
    for _ in range(5):
        eng.submit(_tagged(11))
        eng.flush()
    s = eng.stats()
    assert s["batches"] == 10
    assert len(eng.stats_.batch_lat_s) == 10
    assert 0.0 < s["lat_p50_ms"] <= s["lat_p95_ms"] <= s["lat_p99_ms"]
    assert s["lat_p95_ms"] <= s["wall_s"] * 1e3 + 1e-9
    assert s["dispatch_s"] <= s["wall_s"] + 1e-9
    assert s["depth"] == eng.depth and s["shards"] == 1


def test_view_returning_pipeline_verdicts_survive_buffer_reuse():
    """A plain-numpy pipeline returning a VIEW of its input must not have
    its already-returned verdicts corrupted when the staging ring is
    reused by later batches."""
    eng = PacketServeEngine(lambda x: x[:, 0], feature_dim=2, max_batch=8,
                            depth=2)
    eng.submit(_tagged(40))              # 5 batches > ring size (depth+1)
    first = eng.flush()
    np.testing.assert_array_equal(first, np.arange(40))
    eng.submit(np.full((16, 2), 777.0, np.float32))
    eng.flush()
    # the earlier verdicts must be untouched by the ring reuse
    np.testing.assert_array_equal(first, np.arange(40))
    # a numpy result is already on the host: no copy was started early
    assert eng.stats()["batches"] == 7
    assert _early_copies(eng) == 0


def _early_copies(eng):
    """``stats()["early_copies"]`` after checking its telemetry mirror."""
    tel = eng.telemetry().metrics.get("serve_early_host_copies_total")
    assert tel.value() == eng.stats()["early_copies"]
    return eng.stats()["early_copies"]


class _LazyVerdicts:
    """Stands in for a device result: logs when its host copy is started
    and when it is taken."""

    def __init__(self, rows, tag, log):
        self._v = rows[:, 0].astype(np.int32)   # the staging row is reused
        self._tag, self._log = tag, log

    def copy_to_host_async(self):
        self._log.append(("copy", self._tag))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("fetch", self._tag))
        return self._v


class _LazyPipeline:
    def __init__(self):
        self.log, self.n = [], 0

    def __call__(self, x):
        self.log.append(("dispatch", self.n))
        self.n += 1
        return _LazyVerdicts(x, self.n - 1, self.log)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_verdict_copy_starts_at_dispatch_not_at_fetch(depth):
    """Each batch's copy to the host is started right after its dispatch,
    before any later batch is dispatched and before its own fetch."""
    pipe = _LazyPipeline()
    eng = PacketServeEngine(pipe, feature_dim=2, max_batch=4, depth=depth)
    pipe.log.clear()                     # the warm-up batch
    pipe.n = 0
    eng.submit(_tagged(20))
    np.testing.assert_array_equal(eng.flush(), np.arange(20))
    log = pipe.log
    for k in range(5):
        d, c, f = (log.index((e, k)) for e in ("dispatch", "copy", "fetch"))
        assert c == d + 1 < f
    # the fetches still wait for the ring: at depth d, batch k is taken
    # after batch k + d - 1 is dispatched (arrival order kept)
    for k in range(5 - depth + 1):
        assert log.index(("fetch", k)) > log.index(("copy", k + depth - 1))
    assert [e for e in log if e[0] == "fetch"] == [("fetch", k)
                                                   for k in range(5)]
    assert _early_copies(eng) == eng.stats()["batches"] == 5


def test_requested_pallas_unavailable_reports_interpreter(monkeypatch):
    """backend="pallas" with no Pallas toolchain must SERVE (interpreter)
    and REPORT the interpreter — never the engine that was requested."""
    from repro.core import codegen, feasibility as feas, mlalgos
    from repro.data import netdata

    monkeypatch.setattr(pallas_backend, "pallas_available", lambda: False)
    d = netdata.make_ad_dataset(features=7, n_train=256, n_test=128)
    rep = feas.FeasibilityReport(True, [], {"cu": 1}, 1.0, 1e9)
    pipe = codegen.taurus_codegen(
        "ad", mlalgos.train_dnn(d, hidden=[8], epochs=1, seed=0), rep
    )
    eng = PacketServeEngine(pipe, feature_dim=7, max_batch=32,
                            backend="pallas")
    eng.submit(d.test_x[:50])
    ref = PacketServeEngine(pipe, feature_dim=7, max_batch=32)
    ref.submit(d.test_x[:50])
    np.testing.assert_array_equal(eng.flush(), ref.flush())
    assert eng.stats()["backend"] == "interpret"
    assert eng.stats()["backend_batches"] == {"interpret": 2}


# ------------------------------------------------------- stateful serving


def _flow_pipeline(backend="interpret"):
    spec = FlowStateSpec(n_slots=16, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)
    fk = stageir.FlowKey((0,), spec.n_slots)
    ru = stageir.RegisterUpdate(
        spec, ewma_cols=(1,), hist_cols=(1,),
        hist_edges=(np.linspace(0, 1, 4)[1:-1],),
    )
    ws = stageir.WindowStats(spec, mode="all")
    return StatefulPipeline([fk, ru, ws], backend=backend)


def _flow_packets(rng, n):
    X = np.zeros((n, 2), np.float32)
    X[:, 0] = rng.integers(0, 6, n)
    X[:, 1] = rng.random(n)
    return X


@given(data=st.data())
@HSET
def test_stateful_ragged_interleavings_match_single_pass(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 1000)))
    X = _flow_packets(rng, data.draw(st.integers(1, 120)))
    # reference: one unpadded pass through the pipeline
    ref_pipe = _flow_pipeline()
    ref_state, ref_feats = ref_pipe(ref_pipe.init_state(), X)

    eng = PacketServeEngine(_flow_pipeline(), feature_dim=2,
                            max_batch=data.draw(st.integers(2, 19)),
                            depth=data.draw(st.integers(1, 4)))
    got, pos = [], 0
    while pos < len(X):
        n = min(data.draw(st.integers(1, 31)), len(X) - pos)
        eng.submit(X[pos:pos + n])
        pos += n
        if data.draw(st.booleans()):
            got.append(eng.flush())
    got.append(eng.flush())
    feats = np.concatenate([g for g in got if len(g)])
    # padding rows never leaked into the register file, order preserved
    np.testing.assert_array_equal(np.asarray(eng.state.keys),
                                  np.asarray(ref_state.keys))
    np.testing.assert_array_equal(np.asarray(eng.state.regs),
                                  np.asarray(ref_state.regs))
    np.testing.assert_array_equal(feats, np.asarray(ref_feats))


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_stateful_early_copies_match_synchronous_run(rng, depth):
    """On a jitted stateful pipeline every batch's copy starts at
    dispatch, and the verdicts and registers are those of a strictly
    synchronous run, batch by batch, in arrival order."""
    X = _flow_packets(rng, 90)
    eng = PacketServeEngine(_flow_pipeline(), feature_dim=2, max_batch=8,
                            depth=depth)
    for lo in range(0, 90, 30):
        eng.submit(X[lo:lo + 30])
    got = eng.flush()
    ref_pipe = _flow_pipeline()
    state, ref = ref_pipe.init_state(), []
    for lo in range(0, 90, 8):           # the engine's batches, each taken
        state, v = ref_pipe(state, X[lo:lo + 8])   # before the next
        ref.append(v)
    np.testing.assert_array_equal(got, np.concatenate(ref))
    np.testing.assert_array_equal(np.asarray(eng.state.regs),
                                  np.asarray(state.regs))
    assert eng.stats()["batches"] == 12
    assert _early_copies(eng) == 12


def test_engine_initializes_and_threads_state(rng):
    eng = PacketServeEngine(_flow_pipeline(), feature_dim=2, max_batch=8)
    assert eng.state is not None and eng.state.occupied == 0
    eng.submit(_flow_packets(rng, 20))
    eng.flush()
    assert eng.state.occupied > 0
    # resuming from an existing table continues, not restarts
    resumed = PacketServeEngine(_flow_pipeline(), feature_dim=2,
                                max_batch=8, state=eng.state)
    assert resumed.state.occupied == eng.state.occupied


def _classifier_pipeline():
    """Flow prefix + a fixed MLP classifier (fully kernel-eligible)."""
    base = _flow_pipeline()
    rng = np.random.default_rng(7)
    n_in = base.stages[2].n_out
    w1 = rng.normal(size=(n_in, 6)).astype(np.float32)
    w2 = rng.normal(size=(6, 2)).astype(np.float32)
    mlp = stageir.FusedMLP([w1, w2], [np.zeros(6, np.float32),
                                      np.zeros(2, np.float32)])
    return StatefulPipeline(base.stages + [mlp, stageir.Reduce("argmax")])


@pytest.mark.skipif(not pallas_backend.pallas_available(),
                    reason="Pallas toolchain unavailable")
def test_engine_stateful_backend_rebind_and_parity(rng):
    X = _flow_packets(rng, 50)
    engs = {
        b: PacketServeEngine(_classifier_pipeline(), feature_dim=2,
                             max_batch=16, backend=b)
        for b in ("interpret", "pallas")
    }
    outs = {}
    # the fully-eligible classifier pipeline serves as the single-launch
    # fused form under "pallas"; the interpreter stays itself
    expect = {"interpret": "interpret", "pallas": "pallas-fused-flow"}
    for b, e in engs.items():
        e.submit(X)
        outs[b] = e.flush()
        assert e.stats()["backend"] == expect[b]
    np.testing.assert_array_equal(outs["interpret"], outs["pallas"])
    np.testing.assert_array_equal(np.asarray(engs["interpret"].state.regs),
                                  np.asarray(engs["pallas"].state.regs))


def test_traffic_streams_are_replayable_and_seeded():
    a = traffic.make_stream("port_scan", n_packets=2000, seed=3)
    b = traffic.make_stream("port_scan", n_packets=2000, seed=3)
    np.testing.assert_array_equal(a.packets, b.packets)
    np.testing.assert_array_equal(a.labels, b.labels)
    c1 = list(a.chunks(300))
    c2 = list(a.chunks(300))
    assert len(c1) == len(c2) and all(
        np.array_equal(x, y) for x, y in zip(c1, c2)
    )
    other = traffic.make_stream("port_scan", n_packets=2000, seed=4)
    assert not np.array_equal(a.packets, other.packets)
    with pytest.raises(KeyError):
        traffic.make_stream("nope")


@pytest.mark.parametrize("scenario", traffic.SCENARIOS)
def test_traffic_scenarios_well_formed(scenario):
    s = traffic.make_stream(scenario, n_packets=3000, seed=1)
    assert s.packets.shape[1] == len(traffic.COLUMNS)
    assert s.packets.dtype == np.float32
    # flow ids exact in f32 and consistent with the int column
    np.testing.assert_array_equal(
        s.packets[:, traffic.COL_FLOW].astype(np.int64), s.flow_ids
    )
    assert (s.packets[:, traffic.COL_IPT] >= 0).all()
    has_attack = scenario != "benign"
    assert bool(s.labels.any()) == has_attack
    # per-packet labels match the flow's ground truth
    for fid, lab in list(s.flow_labels.items())[:20]:
        m = s.flow_ids == fid
        if m.any():
            assert (s.labels[m] == lab).all()


def test_reaction_report_counts_packets_to_detection():
    packets = np.zeros((6, 4), np.float32)
    packets[:, 0] = [1, 2, 1, 2, 1, 2]
    stream = traffic.PacketStream(
        "ddos_burst", packets, np.array([0, 1, 0, 1, 0, 1], np.int32),
        packets[:, 0].astype(np.int32), {1: 0, 2: 1},
    )
    verdicts = np.array([0, 0, 1, 0, 0, 1], np.int32)
    rep = traffic.reaction_report(stream, verdicts)
    assert rep["attack_flows"] == 1 and rep["detected_flows"] == 1
    assert rep["reaction_pkts_median"] == 3      # flow 2's 3rd packet
    assert rep["benign_fp_flow_rate"] == 1.0     # flow 1 was flagged once


def test_reaction_report_all_benign_stream_is_json_clean():
    """No attack flows and no detections -> 0.0 sentinels everywhere, not
    NaN: the report must stay json-serializable and aggregation-safe."""
    import json

    stream = traffic.make_stream("benign", n_packets=2000, seed=0)
    rep = traffic.reaction_report(
        stream, np.zeros(stream.n_packets, np.int32)
    )
    assert rep["attack_flows"] == 0 and rep["detected_flows"] == 0
    assert rep["detection_rate"] == 0.0
    assert rep["reaction_pkts_median"] == 0.0
    assert rep["benign_fp_flow_rate"] == 0.0
    vals = [v for v in rep.values() if isinstance(v, float)]
    assert np.isfinite(vals).all()
    assert json.loads(json.dumps(rep)) == rep


def _tiny_stream(n):
    pkts = np.zeros((n, len(traffic.COLUMNS)), np.float32)
    pkts[:, traffic.COL_FLOW] = np.arange(n) % 2
    pkts[:, traffic.COL_LEN] = 500.0
    pkts[:, traffic.COL_IPT] = 1e-3
    fids = pkts[:, traffic.COL_FLOW].astype(np.int32)
    labels = fids % 2
    return traffic.PacketStream("tiny", pkts, labels.astype(np.int32),
                                fids, {0: 0, 1: 1})


@pytest.mark.parametrize("n", [1, 5])
def test_stream_feature_dataset_shorter_than_one_window(n):
    """A stream far shorter than one chunk window still yields a usable,
    finite dataset: both splits non-empty (a single row serves as its own
    train AND test) and identity-safe standardization moments."""
    stages, names = traffic.flow_feature_stages(n_slots=64)
    ds, mu, sd = traffic.stream_feature_dataset(
        _tiny_stream(n), stages, names, sample_every=1
    )
    assert len(ds.train_x) >= 1 and len(ds.test_x) >= 1
    assert np.isfinite(ds.train_x).all() and np.isfinite(ds.test_x).all()
    assert np.isfinite(mu).all() and np.isfinite(sd).all()
    assert (sd > 0).all()              # never divides by zero downstream


def test_stream_feature_dataset_empty_stream_identity_moments():
    stages, names = traffic.flow_feature_stages(n_slots=64)
    ds, mu, sd = traffic.stream_feature_dataset(
        _tiny_stream(0), stages, names, sample_every=1
    )
    assert len(ds.train_x) == 0 and len(ds.test_x) == 0
    np.testing.assert_array_equal(mu, np.zeros_like(mu))
    np.testing.assert_array_equal(sd, np.ones_like(sd))
