"""Closed-loop attack defense: detect, then DROP, inside the pipeline.

Detection alone is half a data-plane ML pipeline; this example closes
the loop.  A SYN-flood scenario trains a per-packet detector on live
per-flow registers, a ``Mitigate`` stage caps the pipeline with a
per-flow ACTION TABLE (same FNV flow key, [hits, since] rows), and a
fresh seed of the attack replays through ``PacketServeEngine``: once a
flow accumulates ``threshold`` positive verdicts its packets are dropped
at line rate — the verdict stream carries the ``MITIGATED`` sentinel and
no packet is ever both dropped and verdicted
([mitigation contract](../docs/pipeline_ir.md#mitigation-contract)).

The replay also shows the scenario suite's topology tools: the stream
split into per-switch views (flows pinned whole to their ingress
switch), and windowed flow stats auto-labeled by the heuristic rules a
controller would use.

  PYTHONPATH=src python examples/attack_defense.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import codegen, feasibility as feas, mlalgos, stageir
from repro.data import traffic
from repro.flowstate import MITIGATED, MitigationSpec, StatefulPipeline
from repro.serve.packet_engine import PacketServeEngine

enable_compile_cache()

N_PACKETS = 8_000
N_SLOTS = 1024
THRESHOLD = 8

# -- 1. train the detector on one seeded SYN-flood stream
train = traffic.make_stream("syn_flood", n_packets=N_PACKETS, seed=0)
stages, names = traffic.flow_feature_stages(n_slots=N_SLOTS)
ds, mu, sd = traffic.stream_feature_dataset(train, stages, names,
                                            sample_every=4)
dnn = mlalgos.train_dnn(ds, hidden=[16, 8], epochs=3, seed=0)
print(f"detector: DNN {dnn.topology['widths']} on {len(names)} register "
      f"features, held-out F1 "
      f"{mlalgos.f1_score(ds.test_y, dnn.predict(ds.test_x)):.4f}")

# -- 2. cap the pipeline with the action table; both register files are
# charged against the target's SRAM (FeasibilityReport.merge)
mit_spec = MitigationSpec(n_slots=4096, mode="drop", threshold=THRESHOLD)
suffix = traffic.fold_input_standardization(codegen.taurus_stages(dnn),
                                            mu, sd)
pipeline = list(stages) + suffix + [stageir.Mitigate(mit_spec)]
merged = feas.flowstate_report(stages[1].spec, "tofino").merge(
    feas.mitigation_report(mit_spec, "tofino"))
print(f"action table: {mit_spec.n_slots} slots "
      f"({mit_spec.sram_bytes / 1024:.0f} KiB), tofino co-residency "
      f"{'fits' if merged.feasible else 'INFEASIBLE'}: {merged.resources}")

# -- 3. replay an unseen seed of the attack through the mitigated
# pipeline: verdicts until the threshold, MITIGATED drops afterwards
replay = traffic.make_stream("syn_flood", n_packets=N_PACKETS, seed=1)
pipe = StatefulPipeline(pipeline, backend="pallas")
eng = PacketServeEngine(pipe, feature_dim=len(traffic.COLUMNS),
                        max_batch=512)
verdicts = np.concatenate(list(eng.serve_stream(replay.chunks(512))))
print(f"\n[{pipe.backend}] served {len(verdicts)} packets: "
      f"{int((verdicts == MITIGATED).sum())} dropped in-pipeline, "
      f"{int(eng.state.mitigated_flows)} flows marked")

react = traffic.reaction_report(replay, verdicts)
print(f"reaction: detect median {react['reaction_pkts_median']:.0f} pkts, "
      f"+lag {react['mitigation_lag_median']:.0f} to first drop, "
      f"{react['leaked_pkts_total']} leaked after, "
      f"benign collateral {react['benign_mitigated_flow_rate']:.1%}")
assert react["leaked_pkts_total"] == 0

# -- 4. the topology view: the same stream as 4 per-switch slices, and
# the controller-style auto-labels from windowed flow stats
views = traffic.switch_streams(replay, 4)
print(f"\ntopology: {[v.n_packets for v in views]} packets/switch, "
      f"composes back to {traffic.compose_streams(views).n_packets}")
labels = traffic.auto_label(traffic.windowed_flow_stats(replay))
truth = {f: l for f, l in replay.flow_labels.items() if f in labels}
agree = np.mean([labels[f] == l for f, l in truth.items()])
print(f"auto-label vs generation ground truth: {agree:.1%} agreement "
      f"over {len(truth)} flows")
