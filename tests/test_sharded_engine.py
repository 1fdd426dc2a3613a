"""ShardedPacketServeEngine: routing, degradation, parity, stream edges.

One-device hosts exercise the full shard_map serving step by forcing
``min_shards=1`` (a 1-ary mesh is still a mesh); the true multi-device
behavior is pinned by subprocess tests that force 4 host CPU devices
(parity, slow; placement without device-to-device copies).  The routing
helpers are pure functions tested directly."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import stageir
from repro.flowstate import FlowStateSpec, StatefulPipeline
from repro.serve import (
    PacketServeEngine,
    ShardedFlowState,
    ShardedPacketServeEngine,
)
from repro.serve.sharded import route_prefix, shard_of_key


def _flow_pipeline(backend="interpret"):
    spec = FlowStateSpec(n_slots=32, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)
    fk = stageir.FlowKey((0,), spec.n_slots)
    ru = stageir.RegisterUpdate(
        spec, ewma_cols=(1,), hist_cols=(1,),
        hist_edges=(np.linspace(0, 1, 4)[1:-1],),
    )
    ws = stageir.WindowStats(spec, mode="all")
    return StatefulPipeline([fk, ru, ws], backend=backend)


def _flow_packets(rng, n, n_flows=12):
    X = np.zeros((n, 2), np.float32)
    X[:, 0] = rng.integers(0, n_flows, n)
    X[:, 1] = rng.random(n)
    return X


# -------------------------------------------------------- routing helpers


def test_flow_key_numpy_twin_matches_traceable(rng):
    fk = stageir.FlowKey((0, 2), 64)
    X = np.zeros((500, 3), np.float32)
    X[:, 0] = rng.integers(0, 1 << 20, 500)
    X[:, 2] = rng.integers(0, 70000, 500)
    np.testing.assert_array_equal(
        fk.apply_keys_np(X), np.asarray(fk.apply_keys(X))
    )


def test_shard_of_key_range_and_determinism(rng):
    keys = rng.integers(0, 1 << 31, 2000).astype(np.int32)
    for d in (1, 2, 3, 8):
        ids = shard_of_key(keys, d)
        assert ids.min() >= 0 and ids.max() < d
        np.testing.assert_array_equal(ids, shard_of_key(keys, d))


def test_route_prefix_respects_capacity_and_order():
    ids = np.array([0, 1, 0, 0, 1, 0])
    m, perm = route_prefix(ids, 2, capacity=2)
    # row 3 is shard 0's third packet: it and everything after must wait
    assert m == 3
    np.testing.assert_array_equal(perm[0], [0, 2])
    np.testing.assert_array_equal(perm[1], [1])
    m_all, perm_all = route_prefix(np.array([0, 1, 1, 0]), 2, capacity=2)
    assert m_all == 4
    np.testing.assert_array_equal(perm_all[1], [1, 2])


# ------------------------------------------------- degradation + parity


def test_degrades_to_base_engine_on_one_device(ad_pipe, ad_data):
    eng = ShardedPacketServeEngine(ad_pipe, feature_dim=7, max_batch=64)
    assert not eng.sharded                   # one-device host
    base = PacketServeEngine(ad_pipe, feature_dim=7, max_batch=64)
    eng.submit(ad_data.test_x[:200])
    base.submit(ad_data.test_x[:200])
    np.testing.assert_array_equal(base.flush(), eng.flush())
    assert eng.stats()["shards"] == 1


def test_degrades_for_bare_callables():
    eng = ShardedPacketServeEngine(
        lambda x: x[:, 0].astype(np.int32), feature_dim=2, max_batch=8,
        min_shards=1,
    )
    assert not eng.sharded                   # nothing to trace


def test_sharded_stateless_parity_one_shard(ad_pipe, ad_data):
    eng = ShardedPacketServeEngine(ad_pipe, feature_dim=7, max_batch=64,
                                   backend="pallas", min_shards=1)
    assert eng.sharded and eng.n_shards == 1
    base = PacketServeEngine(ad_pipe, feature_dim=7, max_batch=64,
                             backend="pallas")
    eng.submit(ad_data.test_x[:333])
    base.submit(ad_data.test_x[:333])
    np.testing.assert_array_equal(base.flush(), eng.flush())


def test_sharded_stateful_parity_one_shard(rng):
    X = _flow_packets(rng, 220)
    base = PacketServeEngine(_flow_pipeline(), feature_dim=2, max_batch=16)
    eng = ShardedPacketServeEngine(_flow_pipeline(), feature_dim=2,
                                   max_batch=16, min_shards=1)
    assert eng.sharded
    base.submit(X)
    eng.submit(X)
    np.testing.assert_array_equal(base.flush(), eng.flush())
    # with one shard the stacked table must equal the single table exactly
    assert isinstance(eng.state, ShardedFlowState)
    np.testing.assert_array_equal(np.asarray(base.state.keys),
                                  np.asarray(eng.state.keys)[0])
    np.testing.assert_array_equal(np.asarray(base.state.regs),
                                  np.asarray(eng.state.regs)[0])
    assert eng.state.occupied == base.state.occupied


# ------------------------------------------------- stream edge behavior


def test_sharded_serve_stream_tail_and_empty_flush(rng):
    eng = ShardedPacketServeEngine(_flow_pipeline(), feature_dim=2,
                                   max_batch=16, min_shards=1)
    # empty flush on a fresh engine: empty verdicts, nothing in flight
    out = eng.flush()
    assert out.shape == (0,) and eng.pending == 0 and eng.in_flight == 0

    X = _flow_packets(rng, 37)               # ragged tail (37 % 16 != 0)
    got = list(eng.serve_stream(iter([X[:5], X[5:20], X[20:]])))
    assert sum(len(g) for g in got) == 37
    ref = PacketServeEngine(_flow_pipeline(), feature_dim=2, max_batch=16)
    ref.submit(X)
    np.testing.assert_array_equal(np.concatenate(got), ref.flush())
    # the tail was flushed: nothing pending, nothing in flight, and a
    # second flush is empty
    assert eng.pending == 0 and eng.in_flight == 0
    assert len(eng.flush()) == 0


def test_sharded_stream_empty_input():
    eng = ShardedPacketServeEngine(_flow_pipeline(), feature_dim=2,
                                   max_batch=16, min_shards=1)
    assert list(eng.serve_stream(iter([]))) == []


# ------------------------------------------- overflow push-back + hot swap


def test_dispatch_routed_pushes_overflow_back(rng):
    """Rows beyond a shard's per-dispatch capacity are requeued at the
    FRONT (arrival order preserved), not dropped: a direct
    ``_dispatch_routed`` of more rows than ``max_batch`` dispatches
    exactly the capacity prefix and a flush serves the rest."""
    eng = ShardedPacketServeEngine(_flow_pipeline(), feature_dim=2,
                                   max_batch=16, min_shards=1)
    assert eng.sharded and eng._sub_batch == 16
    X = _flow_packets(rng, 30)
    m = eng._dispatch_routed(X)
    assert m == 16                     # capacity prefix only
    assert eng.pending == 14           # overflow requeued, not dropped
    out = eng.flush()
    assert len(out) == 30
    ref = PacketServeEngine(_flow_pipeline(), feature_dim=2, max_batch=16)
    ref.submit(X)
    np.testing.assert_array_equal(out, ref.flush())


def test_swap_works_on_degraded_engine():
    """min_shards unreachable on a one-device host -> base-engine path;
    the hot swap must keep working there (it is the base swap)."""
    eng = ShardedPacketServeEngine(
        lambda x: x[:, 0].astype(np.int32), feature_dim=2, max_batch=8,
        min_shards=2,
    )
    assert not eng.sharded
    X = np.zeros((6, 2), np.float32)
    X[:, 0] = np.arange(6)
    eng.submit(X)
    np.testing.assert_array_equal(eng.flush(), np.arange(6))
    eng.swap(lambda x: x[:, 0].astype(np.int32) + 100)
    eng.submit(X)
    np.testing.assert_array_equal(eng.flush(), np.arange(6) + 100)
    assert eng.stats()["swaps"] == 1


def test_sharded_swap_rejects_untraceable_pipeline(ad_pipe):
    eng = ShardedPacketServeEngine(ad_pipe, feature_dim=7, max_batch=64,
                                   min_shards=1)
    assert eng.sharded
    with pytest.raises(ValueError, match="untraceable"):
        eng.swap(lambda x: x[:, 0].astype(np.int32))


def test_sharded_swap_rejects_key_cols_change(rng):
    eng = ShardedPacketServeEngine(_flow_pipeline(), feature_dim=2,
                                   max_batch=16, min_shards=1)
    assert eng.sharded
    spec = FlowStateSpec(n_slots=32, n_counters=1, n_ewma=1,
                         hist_sizes=(3,), ewma_alpha=0.5)
    rekeyed = StatefulPipeline([
        stageir.FlowKey((1,), spec.n_slots),
        stageir.RegisterUpdate(spec, ewma_cols=(1,), hist_cols=(1,),
                               hist_edges=(np.linspace(0, 1, 4)[1:-1],)),
        stageir.WindowStats(spec, mode="all"),
    ])
    with pytest.raises(ValueError, match="key_cols"):
        eng.swap(rekeyed)
    # the rejection is clean: the engine still serves on the old pipeline
    X = _flow_packets(rng, 20)
    eng.submit(X)
    assert len(eng.flush()) == 20 and eng.stats()["swaps"] == 0


# ------------------------------------------------------ real multi-device


@pytest.fixture(scope="module")
def ad_pipe():
    from repro.core import codegen, feasibility as feas, mlalgos
    from repro.data import netdata

    d = netdata.make_ad_dataset(features=7, n_train=1024, n_test=512)
    rep = feas.FeasibilityReport(True, [], {"cu": 1}, 1.0, 1e9)
    return codegen.taurus_codegen(
        "ad", mlalgos.train_dnn(d, hidden=[16, 8], epochs=2, seed=0), rep
    )


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    from repro.core import codegen, feasibility as feas, mlalgos, stageir
    from repro.data import netdata
    from repro.flowstate import FlowStateSpec, StatefulPipeline
    from repro.serve import PacketServeEngine, ShardedPacketServeEngine
    from repro.serve.sharded import shard_of_key

    d = netdata.make_ad_dataset(features=7, n_train=1024, n_test=2048)
    rep = feas.FeasibilityReport(True, [], {"cu": 1}, 1.0, 1e9)
    pipe = codegen.taurus_codegen(
        "ad", mlalgos.train_dnn(d, hidden=[16, 8], epochs=2, seed=0), rep)

    base = PacketServeEngine(pipe, feature_dim=7, max_batch=64,
                             backend="pallas")
    sh = ShardedPacketServeEngine(pipe, feature_dim=7, max_batch=64,
                                  backend="pallas", depth=3)
    assert sh.sharded and sh.n_shards == 4 and sh.stats()["shards"] == 4
    base.submit(d.test_x[:777]); sh.submit(d.test_x[:777])
    np.testing.assert_array_equal(base.flush(), sh.flush())

    def flow_pipe():
        spec = FlowStateSpec(n_slots=16, n_counters=1, n_ewma=1,
                             hist_sizes=(3,), ewma_alpha=0.5)
        fk = stageir.FlowKey((0,), spec.n_slots)
        ru = stageir.RegisterUpdate(
            spec, ewma_cols=(1,), hist_cols=(1,),
            hist_edges=(np.linspace(0, 1, 4)[1:-1],))
        return StatefulPipeline(
            [fk, ru, stageir.WindowStats(spec, mode="all")])

    rng = np.random.default_rng(1)
    X = np.zeros((300, 2), np.float32)
    X[:, 0] = rng.integers(0, 40, 300)
    X[:, 1] = rng.random(300)
    es = ShardedPacketServeEngine(flow_pipe(), feature_dim=2, max_batch=16)
    es.submit(X)
    vs = es.flush()
    # reference: each shard is its own single-table engine fed its rows
    fk = stageir.FlowKey((0,), 16)
    ids = shard_of_key(fk.apply_keys_np(X), 4)
    ref = np.empty_like(vs)
    for s in range(4):
        e = PacketServeEngine(flow_pipe(), feature_dim=2, max_batch=16)
        e.submit(X[ids == s])
        ref[ids == s] = e.flush()
    np.testing.assert_array_equal(vs, ref)
    print("MULTIDEV-OK")
""")


@pytest.mark.slow
def test_multi_device_parity_subprocess():
    """Force 4 host CPU devices in a subprocess: stateless split parity
    and stateful key-partitioned parity vs per-shard references."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MULTIDEV-OK" in proc.stdout


_PLACEMENT_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    from repro.core import stageir
    from repro.flowstate import FlowStateSpec, MitigationSpec, StatefulPipeline
    from repro.serve import PacketServeEngine, ShardedPacketServeEngine
    from repro.serve.sharded import shard_of_key

    SLOTS = 1 << 10

    def flow_pipe(mitigated):
        spec = FlowStateSpec(n_slots=SLOTS, n_counters=1, n_ewma=1,
                             hist_sizes=(3,), ewma_alpha=0.5)
        ws = stageir.WindowStats(spec, mode="all")
        stages = [stageir.FlowKey((0,), SLOTS),
                  stageir.RegisterUpdate(
                      spec, ewma_cols=(1,), hist_cols=(1,),
                      hist_edges=(np.linspace(0, 1, 4)[1:-1],)),
                  ws]
        if mitigated:             # says attack for every packet: drops
            w = np.zeros((ws.n_out, 2), np.float32)
            stages += [stageir.FusedMLP([w], [np.float32([0.0, 1.0])]),
                       stageir.Reduce("argmax"),
                       stageir.Mitigate(MitigationSpec(
                           n_slots=SLOTS, mode="drop", threshold=3))]
        return StatefulPipeline(stages, backend="interpret")

    rng = np.random.default_rng(5)
    X = np.zeros((300, 2), np.float32)
    X[:, 0] = rng.integers(0, 40, 300)
    X[:, 1] = rng.random(300)
    ids = shard_of_key(stageir.FlowKey((0,), SLOTS).apply_keys_np(X), 4)
    for mitigated in (False, True):
        # tables, rows and mask must already sit where the step reads
        # them: any re-slice or copy between devices raises here
        with jax.transfer_guard_device_to_device("disallow"):
            eng = ShardedPacketServeEngine(flow_pipe(mitigated),
                                           feature_dim=2, max_batch=64,
                                           depth=2)
            assert eng.sharded and eng.n_shards == 4
            got = []
            for chunk in np.array_split(X, 3):
                eng.submit(chunk)
                got.append(eng.flush())
        got = np.concatenate(got)
        ref = np.empty_like(got)
        for s in range(4):
            e = PacketServeEngine(flow_pipe(mitigated), feature_dim=2,
                                  max_batch=16)
            e.submit(X[ids == s])
            ref[ids == s] = e.flush()
        np.testing.assert_array_equal(got, ref)
        assert (got < 0).any() == mitigated
        resharded = eng.telemetry().metrics.get("serve_resharded_step_args")
        assert resharded.value() == 0, resharded.value()
    print("PLACEMENT-OK")
""")


def test_sharded_inputs_placed_without_device_copies():
    """Force 4 host CPU devices in a subprocess: a plain and a mitigated
    stateful engine serve under a transfer guard that forbids
    device-to-device copies, match the per-shard references, and count
    no step argument off the step's input sharding."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PLACEMENT-OK" in proc.stdout
