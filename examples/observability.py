"""The unified telemetry plane, live: one replay, every surface.

A mitigated ``coordinated_ddos`` detector (four staggered attack source
groups, in-pipeline ``Mitigate`` drop table) serves a fresh replay while
an operator watches (docs/pipeline_ir.md#telemetry-contract):

  * a ``DriftDetector`` armed with a BENIGN-traffic snapshot fires as
    the flood onsets shift the packet mix, a background thread retrains
    on the buffered windows, and the new model installs via atomic
    ``engine.swap`` — every step journaled (drift -> retrain_start ->
    retrain_done -> hot_swap) with monotonic timestamps;
  * the action table engages mid-replay (``mitigation_engage`` events,
    ``serve_mitigated_packets_total`` counting dropped packets);
  * a live dashboard renders the metrics registry every few windows:
    throughput, latency percentiles, flow-table occupancy/evictions,
    drain-vs-lockstep schedule shape, mitigation residency;
  * at the end the plane exports everything an operator would mount:
    Prometheus text, the Chrome trace (load in chrome://tracing or
    Perfetto), and the JSON-lines event journal.

  PYTHONPATH=src python examples/observability.py
"""

import json
import os
import tempfile

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import codegen, mlalgos, stageir
from repro.data import traffic
from repro.flowstate import (
    MITIGATED,
    DriftDetector,
    DriftSnapshot,
    MitigationSpec,
    StatefulPipeline,
)
from repro.serve import HotSwapController, PacketServeEngine

enable_compile_cache()

CHUNK = 512
N_PACKETS = 12_000
N_SLOTS = 2048
MIT_SLOTS = 4096
THRESHOLD = 8
SCENARIO = "coordinated_ddos"

OUT_DIR = tempfile.mkdtemp(prefix="observability-")
JOURNAL = os.path.join(OUT_DIR, "journal.jsonl")
TRACE = os.path.join(OUT_DIR, "trace.json")
PROM = os.path.join(OUT_DIR, "metrics.prom")

stages, names = traffic.flow_feature_stages(n_slots=N_SLOTS)


def train_pipeline(stream, tag: str) -> StatefulPipeline:
    """Detector + drop-mode action table on the stream's ground truth."""
    ds, mu, sd = traffic.stream_feature_dataset(stream, stages, names,
                                                sample_every=4)
    dnn = mlalgos.train_dnn(ds, hidden=[16, 8], epochs=3, seed=0)
    suffix = traffic.fold_input_standardization(
        codegen.taurus_stages(dnn), mu, sd)
    mit = stageir.Mitigate(MitigationSpec(
        n_slots=MIT_SLOTS, mode="drop", threshold=THRESHOLD))
    print(f"  [{tag}] detector trained "
          f"(test F1 {mlalgos.f1_score(ds.test_y, dnn.predict(ds.test_x)):.3f})")
    return StatefulPipeline(list(stages) + suffix + [mit],
                            backend="pallas")


def windows_to_stream(windows, flow_labels) -> traffic.PacketStream:
    pkts = np.concatenate(windows, 0)
    fids = pkts[:, traffic.COL_FLOW].astype(np.int32)
    labels = np.array([flow_labels.get(int(f), 0) for f in fids], np.int32)
    return traffic.PacketStream(f"{SCENARIO}-retrain", pkts, labels,
                                fids, dict(flow_labels))


def _one(snap, name, default=0):
    m = snap.get(name)
    return m["values"][0]["value"] if m and m["values"] else default


def dashboard(engine, tel, served: int, total: int) -> None:
    """One operator-dashboard frame from the live registry + journal."""
    snap = tel.snapshot()
    s = engine.stats()
    drain = _one(snap, "flow_drain_batches_total")
    lockstep = _one(snap, "flow_lockstep_batches_total")
    line = (f"  [{served:6d}/{total}] "
            f"{s['pkt_per_s']:9,.0f} pkt/s  p95 {s['lat_p95_ms']:5.2f} ms"
            f" | table {_one(snap, 'flow_occupancy_frac'):5.1%} full, "
            f"{_one(snap, 'flow_evictions_total'):4.0f} evict"
            f" | sched {lockstep:.0f}L/{drain:.0f}D"
            f" | marked {_one(snap, 'flow_mit_marked'):4.0f} flows, "
            f"dropped {_one(snap, 'serve_mitigated_packets_total'):5.0f}"
            f" | swaps {_one(snap, 'serve_swaps_total'):.0f}")
    events = tel.journal.events()
    if events:
        last = events[-1]
        extra = {k: v for k, v in last.items()
                 if k not in ("seq", "t_s", "wall", "kind")}
        line += f"\n           last event: {last['kind']} {extra}"
    print(line)


# -- 1. train on one seed, arm drift detection against BENIGN traffic
print(f"== train mitigated {SCENARIO} detector ==")
train_stream = traffic.make_stream(SCENARIO, n_packets=N_PACKETS, seed=0)
pipe = train_pipeline(train_stream, "initial")

benign = traffic.make_stream("benign", n_packets=N_PACKETS, seed=0)
snapshot = DriftSnapshot.from_packets(
    benign.packets, cols=(traffic.COL_LEN,), window=CHUNK)
detector = DriftDetector(snapshot, alpha=0.3, threshold=1.2, patience=2)

# -- 2. serve a FRESH replay with the full plane on (the default)
replay = traffic.make_stream(SCENARIO, n_packets=N_PACKETS, seed=1)
engine = PacketServeEngine(pipe, feature_dim=len(traffic.COLUMNS),
                           max_batch=CHUNK, depth=2,
                           telemetry=None)   # default: private full plane
tel = engine.telemetry()                     # in-memory; dumped at the end


def retrain(windows):
    print(f"           drift fired (score {detector.score:.2f}) -> "
          f"background retrain on {len(windows)} buffered windows")
    return train_pipeline(
        windows_to_stream(windows, replay.flow_labels), "retrain")


ctrl = HotSwapController(engine, detector, retrain, buffer_windows=12)

print(f"\n== live replay ({N_PACKETS} packets, dashboard every 4 windows,"
      " L=lockstep D=drain batches) ==")
verdicts, served = [], 0
for i, chunk in enumerate(replay.chunks(CHUNK)):
    ctrl.observe(chunk)
    engine.submit(chunk)
    verdicts.append(engine.flush())
    served += len(chunk)
    if i % 4 == 3:
        dashboard(engine, tel, served, N_PACKETS)
verdicts = np.concatenate(verdicts)

assert ctrl.wait(600), "background retrain did not finish"
assert not ctrl.errors, ctrl.errors
engine.flush()                               # install boundary for the swap
dashboard(engine, tel, served, N_PACKETS)

# -- 3. the operator's story, straight from the journal
print("\n== operator event journal (full trail) ==")
events = tel.journal.events()
for e in events:
    extra = {k: v for k, v in e.items()
             if k not in ("seq", "t_s", "wall", "kind")}
    print(f"  #{e['seq']:<3d} t={e['t_s']:8.3f}s  {e['kind']:<18s} {extra}")

kinds = tel.journal.kinds()
assert {"drift", "retrain_start", "retrain_done", "hot_swap",
        "mitigation_engage"} <= kinds, kinds
ts = [e["t_s"] for e in events]
assert ts == sorted(ts), "journal timestamps must be monotonic"
assert len(verdicts) == replay.n_packets, "packets dropped by observation?"

snap = tel.snapshot()
assert _one(snap, "serve_packets_total") == replay.n_packets
dropped = int((verdicts == MITIGATED).sum())
assert _one(snap, "serve_mitigated_packets_total") == dropped

# -- 4. export every surface
tel.journal.dump(JOURNAL)
with open(TRACE, "w") as f:
    json.dump(tel.chrome_trace(), f)
prom = tel.prometheus()
with open(PROM, "w") as f:
    f.write(prom)

print("\n== prometheus exposition (excerpt) ==")
for line in prom.splitlines():
    if line.startswith(("serve_packets_total", "serve_mitigated",
                        "flow_occupancy", "flow_mit_marked",
                        "serve_batch_latency_ms_bucket{le=\"5\"",
                        "serve_swaps_total")):
        print(f"  {line}")

# detection -> mitigation lag, from the replay's ground truth
react = traffic.reaction_report(replay, verdicts)
print(f"\n== reaction (ground truth vs enforced) ==")
print(f"  detection rate {react['detection_rate']:.3f}  "
      f"reaction median {react['reaction_pkts_median']:.0f} pkts  "
      f"mitigation lag median {react['mitigation_lag_median']:.0f} pkts  "
      f"leaked after first drop {react['leaked_pkts_total']}")

n_spans = len(tel.tracer.spans())
print(f"\nexports -> {OUT_DIR}")
print(f"  journal.jsonl  {len(events)} events "
      f"(drift -> retrain -> hot_swap -> mitigation)")
print(f"  trace.json     {n_spans} spans — load in chrome://tracing")
print(f"  metrics.prom   {len(prom.splitlines())} lines")
print(f"\n{dropped} attack packets dropped in-pipeline, "
      f"{int(_one(snap, 'flow_mit_marked'))} flows marked, one hot swap "
      "mid-mitigation — and the whole story is in the journal.")
