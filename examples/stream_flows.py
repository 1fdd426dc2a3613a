"""Streaming botnet/DDoS detection on live per-flow state (paper §5.1.1).

examples/botnet_pipeline.py evaluates per-packet reaction time on
PRECOMPUTED partial histograms; this example closes the loop: a synthetic
DDoS-burst packet stream (repro/data/traffic.py) flows through a STATEFUL
pipeline — ``FlowKey -> RegisterUpdate`` maintains per-flow counters,
EWMAs and windowed histograms in a fixed-slot register file, and a DNN
classifies every packet on its flow's live register row — on both
execution engines (jitted reference vs ONE fused Pallas launch covering
registers AND classifier, ``pallas-fused-flow``, bit-identical
verdicts), reporting the per-part backend, pkt/s, per-batch latency
percentiles and reaction-time percentiles (packets until a flow's first
correct verdict).

  PYTHONPATH=src python examples/stream_flows.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import codegen, feasibility as feas, mlalgos
from repro.data import traffic
from repro.flowstate import StatefulPipeline
from repro.serve.packet_engine import PacketServeEngine

enable_compile_cache()

N_SLOTS = 2048
N_PACKETS = 16_000

# -- 1. train a per-packet flow classifier on a seeded DDoS-burst stream
train_stream = traffic.make_stream("ddos_burst", n_packets=N_PACKETS,
                                   seed=0)
stages, names = traffic.flow_feature_stages(n_slots=N_SLOTS)
ds, mu, sd = traffic.stream_feature_dataset(train_stream, stages, names,
                                            sample_every=2)
dnn = mlalgos.train_dnn(ds, hidden=[16, 8], epochs=3, seed=0)
f1 = mlalgos.f1_score(ds.test_y, dnn.predict(ds.test_x))
print(f"flow classifier: DNN {dnn.topology['widths']} "
      f"on {len(names)} register features, held-out F1 {f1:.4f}")

# feasibility: the register file co-resides with the model on the target
# (FeasibilityReport.merge — resources add, throughput is the min)
spec = stages[1].spec
print(f"register file: {spec.n_slots} slots x {spec.width} words "
      f"({spec.sram_bytes / 1024:.0f} KiB)")
for plat in ("taurus", "tpu"):
    rep = feas.flowstate_report(spec, plat)
    verdict = "fits" if rep.feasible else f"INFEASIBLE ({rep.reasons[0]})"
    print(f"  {plat:6s} {verdict}: {rep.resources}")

# -- 2. assemble the stateful pipeline: registers + classifier, with the
# training-time standardization folded into the first dense layer so the
# served pipeline consumes RAW register rows
suffix = traffic.fold_input_standardization(codegen.taurus_stages(dnn),
                                            mu, sd)
pipeline_stages = list(stages) + suffix

# -- 3. replay a fresh (unseen seed) stream through both engines
eval_stream = traffic.make_stream("ddos_burst", n_packets=N_PACKETS,
                                  seed=1)
verdicts = {}
for backend in ("interpret", "pallas"):
    pipe = StatefulPipeline(pipeline_stages, backend=backend)
    eng = PacketServeEngine(pipe, feature_dim=len(traffic.COLUMNS),
                            max_batch=512)
    got = [v for v in eng.serve_stream(eval_stream.chunks(512))]
    verdicts[backend] = np.concatenate(got)
    s = eng.stats()
    print(f"\n[{s['backend']}] {pipe!r}")
    # per-part backend report: which engine serves each half of the
    # pipeline — or ONE fused launch covering both (pallas-fused-flow)
    part = ("fused single launch" if pipe.fused
            else f"flow={pipe.flow_backend}  "
                 f"classifier={pipe.classifier_backend}")
    print(f"  parts: {part}")
    print(f"  {s['packets']} packets, {s['pkt_per_s']:,.0f} pkt/s, "
          f"{s['batches']} batches, {s['pad_packets']} pad rows")
    print(f"  per-batch latency: p50 {s['lat_p50_ms']:.3f} ms, "
          f"p95 {s['lat_p95_ms']:.3f} ms, p99 {s['lat_p99_ms']:.3f} ms")

assert pipe.backend == "pallas-fused-flow", pipe.backend

assert np.array_equal(verdicts["interpret"], verdicts["pallas"]), \
    "the two engines must produce bit-identical verdicts (same registers)"

# -- 4. reaction time: packets until each attack flow's first detection
rep = traffic.reaction_report(eval_stream, verdicts["pallas"])
print(f"\nreaction time on the DDoS burst ({rep['attack_flows']} attack "
      f"flows among {eval_stream.n_flows}):")
print(f"  detection rate        {rep['detection_rate']:.1%}")
print(f"  packets-to-detection  median {rep['reaction_pkts_median']:.0f}, "
      f"p95 {rep['reaction_pkts_p95']:.0f}")
print(f"  benign flows flagged  {rep['benign_fp_flow_rate']:.1%}")
print("\nFlowLens-style detectors wait for the full flow; this pipeline "
      "reacts within packets on live register state.")
