"""Zero-downtime hot swap: drift -> background retrain -> atomic install.

The compiler stages (DSE -> training -> codegen) train offline, but live
traffic drifts.  This example closes the redeployment loop
(docs/pipeline_ir.md#hot-swap-contract) on the ``concept_drift``
scenario, whose attack signature SHIFTS mid-stream — phase A is a
tiny-packet volumetric flood, phase B a stealth MTU flood shaped like
benign bulk traffic:

  1. train the initial model on phase-A traffic through the batched DSE
     racer (``core.dse.retrain_model``);
  2. serve a fresh stream live; a ``DriftDetector`` watches the packet
     windows against a frozen phase-A snapshot, fires when the mix
     shifts, and a ``HotSwapController`` retrains on the drifted windows
     in a BACKGROUND thread (``core.traincache.GLOBAL_CACHE``
     warm-starts repeat episodes) while the engine keeps serving;
  3. the retrained pipeline installs via ``engine.swap`` at a
     dispatch-ring boundary: no batch dropped (verdict count == packet
     count), register state carried bit-identically (same
     ``FlowStateSpec``), and F1 on drifted traffic recovers —
     demonstrated on BOTH ``PacketServeEngine`` and
     ``ShardedPacketServeEngine``.

  PYTHONPATH=src python examples/hot_swap.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import codegen, dse, mlalgos
from repro.core.alchemy import Platforms
from repro.core.traincache import GLOBAL_CACHE
from repro.data import traffic
from repro.flowstate import DriftDetector, DriftSnapshot, StatefulPipeline
from repro.serve import (
    HotSwapController,
    PacketServeEngine,
    ShardedPacketServeEngine,
)

enable_compile_cache()

CHUNK = 512
N_PACKETS = 24_000
N_SLOTS = 2048
SPAN_S = 120.0
SEARCH = dict(algorithms=["dnn"], budget=6, n_init=3, seed=0)

platform = Platforms.Taurus()
platform.constrain(resources={"rows": 16, "cols": 16})

stages, names = traffic.flow_feature_stages(n_slots=N_SLOTS)


def drift_index(stream) -> int:
    """First packet index of phase B (the shifted attack signature)."""
    return int(np.searchsorted(stream.times, SPAN_S * traffic.DRIFT_FRAC))


def search_pipeline(stream, tag: str) -> StatefulPipeline:
    """Features -> batched DSE racer -> stateful serving pipeline, with
    the training-time standardization folded into the first dense layer
    (the served pipeline consumes raw register rows)."""
    ds, mu, sd = traffic.stream_feature_dataset(stream, stages, names,
                                                sample_every=2)
    res = dse.retrain_model(platform, ds, name=tag, **SEARCH)
    suffix = traffic.fold_input_standardization(
        codegen.taurus_stages(res.trained), mu, sd
    )
    print(f"  [{tag}] DSE winner {res.algorithm} "
          f"F1 {res.value:.3f} in {res.wall_s:.1f}s "
          f"(cache: {GLOBAL_CACHE.stats()})")
    return StatefulPipeline(list(stages) + suffix)


def windows_to_stream(windows, flow_labels) -> traffic.PacketStream:
    """The drifted-window retrain corpus as a labeled stream.  Labeling
    policy: scenario ground truth (production systems would use slow-path
    annotation or delayed feedback)."""
    pkts = np.concatenate(windows, 0)
    fids = pkts[:, traffic.COL_FLOW].astype(np.int32)
    labels = np.array([flow_labels.get(int(f), 0) for f in fids], np.int32)
    return traffic.PacketStream("concept_drift-retrain", pkts, labels,
                                fids, dict(flow_labels))


# -- 1. initial model: phase A only (the world before the drift)
print("== train initial model on phase-A traffic ==")
train_stream = traffic.make_stream("concept_drift", n_packets=N_PACKETS,
                                   seed=0)
phase_a = train_stream.slice(0, drift_index(train_stream))
initial_pipe = search_pipeline(phase_a, "phase-a")

# the frozen training-time snapshot the drift statistic scores against
snapshot = DriftSnapshot.from_packets(
    phase_a.packets, cols=(traffic.COL_LEN,), window=CHUNK
)

# the serving stream (fresh seed): drifts at DRIFT_FRAC of the span
eval_stream = traffic.make_stream("concept_drift", n_packets=N_PACKETS,
                                  seed=1)
ev_drift = drift_index(eval_stream)
# fresh drifted traffic served AFTER the swap (the recovery segment)
rec_stream = traffic.make_stream("concept_drift", n_packets=N_PACKETS,
                                 seed=2)
rec_stream = rec_stream.slice(drift_index(rec_stream))


def serve_with_hot_swap(engine, label: str) -> dict:
    detector = DriftDetector(snapshot, alpha=0.25, threshold=1.9,
                             patience=3)

    def retrain(windows):
        print(f"  [{label}] drift fired after {detector.windows} windows "
              f"(score {detector.score:.2f}) -> background retrain on "
              f"{len(windows)} buffered windows")
        return search_pipeline(
            windows_to_stream(windows, eval_stream.flow_labels), "retrain"
        )

    ctrl = HotSwapController(engine, detector, retrain, buffer_windows=24)

    # serve the whole drifting stream; the controller watches every
    # window and launches the retrain mid-stream, the engine keeps
    # serving the old model until the swap installs at a ring boundary
    verdicts = []
    for chunk in eval_stream.chunks(CHUNK):
        ctrl.observe(chunk)
        engine.submit(chunk)
        verdicts.append(engine.flush())
    verdicts = np.concatenate(verdicts)

    assert ctrl.episodes == 1, f"drift never fired ({detector.report()})"
    assert ctrl.wait(600), "retrain did not finish"
    assert not ctrl.errors, ctrl.errors

    # force the install boundary, asserting bit-identical state carry:
    # the swap shares the FlowStateSpec, so the live table must survive
    # the install untouched, bit for bit
    pre_keys = np.array(engine.state.keys)
    pre_regs = np.array(engine.state.regs)
    swaps_before = engine.stats_.swaps
    engine.flush()
    assert engine.stats_.swaps == swaps_before + 1, "swap did not install"
    np.testing.assert_array_equal(pre_keys, np.asarray(engine.state.keys))
    np.testing.assert_array_equal(pre_regs, np.asarray(engine.state.regs))

    # recovery segment: fresh drifted traffic on the NEW model
    rec_verdicts = []
    for chunk in rec_stream.chunks(CHUNK):
        engine.submit(chunk)
        rec_verdicts.append(engine.flush())
    rec_verdicts = np.concatenate(rec_verdicts)

    # zero-downtime accounting: nothing dropped on either side of the swap
    assert len(verdicts) == eval_stream.n_packets
    assert len(rec_verdicts) == rec_stream.n_packets

    stats = engine.stats()
    off = min(stats["swap_pkt_offsets"][0], eval_stream.n_packets)
    f1 = mlalgos.f1_score
    report = {
        "f1_pre_drift": f1(eval_stream.labels[:ev_drift],
                           verdicts[:ev_drift]),
        "f1_post_drift": f1(eval_stream.labels[ev_drift:off],
                            verdicts[ev_drift:off]),
        "f1_post_swap": f1(rec_stream.labels, rec_verdicts),
        "swap_lat_ms": stats["swap_lat_ms"][0],
        "swaps": stats["swaps"],
        "backend_batches": engine.stats_.backend_batches,
    }
    print(f"  [{label}] F1 pre-drift {report['f1_pre_drift']:.3f} -> "
          f"drifted {report['f1_post_drift']:.3f} -> post-swap "
          f"{report['f1_post_swap']:.3f}; swap parked->installed in "
          f"{report['swap_lat_ms']:.1f} ms")
    assert report["f1_pre_drift"] > 0.85, report
    assert report["f1_post_drift"] < 0.5, report
    assert report["f1_post_swap"] > 0.85, report
    return report


print("\n== live serve + hot swap: PacketServeEngine (depth=2) ==")
base_report = serve_with_hot_swap(
    PacketServeEngine(initial_pipe, feature_dim=len(traffic.COLUMNS),
                      max_batch=CHUNK, depth=2),
    "base",
)

print("\n== live serve + hot swap: ShardedPacketServeEngine ==")
# min_shards=1: the full shard_map serving step, whatever the host has;
# the SECOND retrain episode replays the first's training jobs out of
# GLOBAL_CACHE (content-addressed), so the background search is warm
hits_before = GLOBAL_CACHE.stats()["hits"]
sharded_engine = ShardedPacketServeEngine(
    initial_pipe, feature_dim=len(traffic.COLUMNS), max_batch=CHUNK,
    depth=2, min_shards=1,
)
assert sharded_engine.sharded, "shard_map path must engage (min_shards=1)"
sharded_report = serve_with_hot_swap(sharded_engine, "sharded")
hits_gained = GLOBAL_CACHE.stats()["hits"] - hits_before
print(f"  warm retrain: +{hits_gained} trained-candidate cache hits")
assert hits_gained > 0, "second retrain episode should warm-start"

print("\n== summary ==")
for label, rep in (("base", base_report), ("sharded", sharded_report)):
    print(f"  {label:8s} F1 {rep['f1_pre_drift']:.3f} -> "
          f"{rep['f1_post_drift']:.3f} -> {rep['f1_post_swap']:.3f}   "
          f"swap {rep['swap_lat_ms']:.1f} ms   "
          f"batches {rep['backend_batches']}")
print("\nthe model was replaced mid-stream with zero dropped batches and "
      "bit-identical register carry-over — the ROADMAP's online-learning "
      "loop, closed.")
