"""Serve packets through a generated pipeline — the data plane in action.

Generates the AD pipeline (fused-MLP Pallas artifact), then streams batched
"packets" through it on BOTH execution engines — the jitted stage
interpreter and the Pallas backend (whole pipeline as one fused kernel
launch, docs/pipeline_ir.md#pallas-lowering-contract) — reporting CPU wall
throughput per engine and the projected TPU roofline throughput the
feasibility oracle promised.

  PYTHONPATH=src python examples/serve_packets.py
"""

import time

import numpy as np

import homunculus
from homunculus.alchemy import DataLoader, Model, Platforms
from repro.compile_cache import enable_compile_cache
from repro.data import netdata

enable_compile_cache()


@DataLoader
def ad_loader():
    return netdata.make_ad_dataset(features=7, n_train=4096, n_test=2048)


model = Model({
    "optimization_metric": ["f1"],
    "algorithm": ["dnn"],
    "name": "ad",
    "data_loader": ad_loader,
})

# TPU backend: the beyond-paper target — same Alchemy program, new platform
platform = Platforms.TPU()
platform.constrain(performance={"throughput": 0.01, "latency": 1e6},
                   resources={"batch": 256})
platform.schedule(model)
res = homunculus.generate(platform, budget=10, n_init=5, seed=0)
r = res["ad"]
print("generated:", r.summary())

data = ad_loader()
pipe = r.pipeline
print("stage list:", [s.kind for s in pipe.stages])

# stream packets through the micro-batching engine on both execution
# engines: fixed batch shape -> compiled once per engine
from repro.serve.packet_engine import PacketServeEngine

verdict_sets = {}
for backend in ("interpret", "pallas"):
    eng = PacketServeEngine(pipe, feature_dim=data.num_features,
                            max_batch=256, backend=backend)
    t0 = time.perf_counter()
    malicious = 0
    chunks = (data.test_x[s:s + 97] for s in range(0, len(data.test_x), 97))
    got = []
    for verdicts in eng.serve_stream(chunks):
        malicious += int(np.sum(verdicts == 1))
        got.append(verdicts)
    wall = time.perf_counter() - t0
    stats = eng.stats()
    n_packets = stats["packets"]
    verdict_sets[backend] = np.concatenate(got)

    print(f"\n[{stats['backend']}] streamed {n_packets} packets in "
          f"{wall:.2f}s ({stats['pkt_per_s']:,.0f} pkt/s pipeline-only, "
          f"{stats['batches']} micro-batches, {stats['pad_packets']} pad rows, "
          f"depth {stats['depth']})")
    print(f"per-batch latency: p50 {stats['lat_p50_ms']:.3f} ms, "
          f"p95 {stats['lat_p95_ms']:.3f} ms, p99 {stats['lat_p99_ms']:.3f} ms"
          f" (host dispatch {stats['dispatch_s'] * 1e3:.1f} ms total)")
    print(f"flagged malicious: {malicious} ({malicious / n_packets:.1%})")

assert np.array_equal(verdict_sets["interpret"], verdict_sets["pallas"]), \
    "the two execution engines must agree bit-for-bit on dense pipelines"
print(f"TPU roofline projection (oracle): "
      f"{r.report.throughput_pps:,.0f} pkt/s, "
      f"latency {r.report.latency_ns / 1e3:.1f} us/batch")
