"""Whole-DAG JIT vs interpreted chaining vs Pallas backends: pkt/s bench.

Builds a 3-model chain (DNN gate > SVM | KMeans) on the AD dataset, then
measures end-to-end packet throughput:

  * interpreted — ``chaining.run_dag``: each model's pipeline runs as its
    own jitted call, verdicts merge in numpy between stages;
  * compiled    — ``chaining.compile_dag``: the whole DAG is ONE jitted
    XLA program (stage lists inlined, gating as jnp.where masks);
  * pallas      — ``compile_dag(..., backend="pallas")``: kernel-eligible
    pipelines inside the DAG run as fused Pallas kernel launches
    (docs/pipeline_ir.md#pallas-lowering-contract).

A second table pins the fused-DAG megakernel on the chained AD > TC
pipeline: ``backend="pallas"`` fuses the whole DAG into ONE kernel launch
(``pallas-fused-dag``) and must serve >= 1.5x the per-model-launch
baseline (``fuse_dag=False`` — the PR-4 path) in pkt/s, bit-exact vs
``run_dag``.  A third table pins the per-pipeline Pallas >= interpreter
gate on the fused-MLP pipeline.  All comparisons use best-of-rounds
timing (shared-runner noise).  Serve-engine stats (pkt/s + latency
percentiles per engine x backend) are recorded for the consolidated
``BENCH_serve.json`` that ``benchmarks/run.py`` emits.

  PYTHONPATH=src python -m benchmarks.dag_throughput
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from repro.core import chaining, codegen, feasibility as feas, mlalgos
from repro.core.alchemy import Model
from repro.data import netdata
from repro.serve import PacketServeEngine, ShardedPacketServeEngine

from benchmarks.common import (
    bench_pps,
    bench_pps_best,
    render_table,
    save_result,
    sharded_stats,
)

BATCHES = (256, 1024, 4096)
# the megakernel's biggest win is launch-overhead-dominated small
# micro-batches (the latency-bound serving regime), so its table starts
# one step lower
FUSED_BATCHES = (128, 256, 1024, 4096)
REPEATS = 20
FUSED_DAG_GATE = 1.5               # megakernel vs per-model-launch baseline


def _noop_loader():
    return None


def _leaf(name: str) -> Model:
    return Model({"name": name, "data_loader": _noop_loader,
                  "algorithm": None})


def build_chain(seed: int = 0):
    d = netdata.make_ad_dataset(features=7, n_train=4096, n_test=8192)
    rep = feas.FeasibilityReport(True, [], {"cu": 1}, 1.0, 1e9)
    dnn = mlalgos.train_dnn(d, hidden=[16, 8], epochs=4, seed=seed)
    svm = mlalgos.train_svm(d, epochs=6, seed=seed)
    km = mlalgos.train_kmeans(d, k=4, seed=seed)
    pipes = {
        "ad": codegen.taurus_codegen("ad", dnn, rep),
        "tc": codegen.taurus_codegen("tc", svm, rep),
        "cl": codegen.taurus_codegen("cl", km, rep),
    }
    node = _leaf("ad") > (_leaf("tc") | _leaf("cl"))
    return d, node, pipes


def bench(fn, X, repeats: int = REPEATS) -> float:
    return bench_pps(fn, X, repeats)


def _serve_stat(pipeline, d, *, label: str, engine_cls=PacketServeEngine,
                max_batch: int = 1024, depth: int = 2, passes: int = 3
                ) -> dict:
    """Stream the test set through a serving engine; -> one BENCH_serve
    entry (pkt/s + p50/p95/p99 pipeline latency)."""
    eng = engine_cls(pipeline, feature_dim=d.num_features,
                     max_batch=max_batch, depth=depth)
    chunks = [d.test_x[s:s + 997] for s in range(0, len(d.test_x), 997)]
    for _ in range(passes):
        for _v in eng.serve_stream(iter(chunks)):
            pass
    s = eng.stats()
    return {
        "engine": engine_cls.__name__,
        "pipeline": label,
        "backend": s["backend"],
        "depth": s["depth"],
        "shards": s["shards"],
        "pkt_per_s": s["pkt_per_s"],
        "lat_p50_ms": s["lat_p50_ms"],
        "lat_p95_ms": s["lat_p95_ms"],
        "lat_p99_ms": s["lat_p99_ms"],
    }


def sharded_run() -> dict:
    """The ad>tc DAG served by ``ShardedPacketServeEngine`` on four
    devices -> its BENCH_serve row."""
    import jax

    assert len(jax.devices()) == 4, jax.devices()
    d, _node, pipes = build_chain()
    stat = _serve_stat(
        chaining.compile_dag(_leaf("ad") > _leaf("tc"), pipes,
                             backend="pallas"),
        d, label="ad>tc", engine_cls=ShardedPacketServeEngine)
    assert stat["shards"] == 4, stat
    return stat


def _sharded_serve_stat() -> dict | None:
    """The ShardedPacketServeEngine row on four devices
    (``common.sharded_stats``), so ``shards`` records the actual device
    count — a run on one device degrades to the base engine and would
    claim a sharded number it never earned.  None (no row) on an
    accelerator host with fewer than four chips."""
    return sharded_stats("benchmarks.dag_throughput", "sharded_run")


def bench_fused_dag(d, pipes) -> dict:
    """The megakernel tables: chained AD > TC, one launch vs per-model.

    Two comparisons, both bit-exact vs ``run_dag``:

    * **direct calls** — the megakernel launch alone must not lose to
      per-model launches (>= 1x at its best batch; the kernel-level
      honesty gate);
    * **serving path** — the PR's hot path (megakernel + overlap engine,
      ``depth>1``) vs the PR-4 serving baseline (per-model launches,
      synchronous ``depth=1`` engine) must reach ``FUSED_DAG_GATE`` pkt/s
      at its best micro-batch.  Baseline and new path are timed in
      interleaved rounds so load drift on shared runners hits both."""
    import time as _time

    node = _leaf("ad") > _leaf("tc")
    per_model = chaining.compile_dag(node, pipes, backend="pallas",
                                     fuse_dag=False)
    fused = chaining.compile_dag(node, pipes, backend="pallas")
    assert fused.backend == "pallas-fused-dag", (
        f"AD > TC must fuse into the megakernel, got {fused.backend}"
    )
    assert per_model.backend == "pallas", per_model.backend

    ref = chaining.run_dag(node, pipes, d.test_x)
    assert np.array_equal(ref, fused(d.test_x)), "megakernel diverged"
    assert np.array_equal(ref, per_model(d.test_x)), "per-model diverged"

    rows = []
    for n in FUSED_BATCHES:
        X = d.test_x[:n]
        base_pps = bench_pps_best(per_model, X)
        mega_pps = bench_pps_best(fused, X)
        rows.append({
            "batch": n,
            "permodel_pps": round(base_pps),
            "megakernel_pps": round(mega_pps),
            "speedup": round(mega_pps / base_pps, 2),
        })

    print("\n== fused-DAG megakernel vs per-model launches "
          "(AD > TC, direct calls, pkt/s) ==")
    print(render_table(
        rows, ["batch", "permodel_pps", "megakernel_pps", "speedup"]
    ))
    best_direct = max(r["speedup"] for r in rows)

    # ---- serving path: overlap engine + megakernel vs PR-4 baseline
    stream = np.concatenate([d.test_x] * 4)
    chunks = [stream[s:s + 2048] for s in range(0, len(stream), 2048)]

    def engine_pps(dag, depth: int, max_batch: int) -> Callable[[], float]:
        eng = PacketServeEngine(dag, feature_dim=d.num_features,
                                max_batch=max_batch, depth=depth)

        def one_round() -> float:
            t0 = _time.perf_counter()
            n = 0
            for v in eng.serve_stream(iter(chunks)):
                n += len(v)
            return n / (_time.perf_counter() - t0)

        return one_round

    serve_rows = []
    for max_batch, depth in ((256, 2), (512, 2), (1024, 3), (2048, 3)):
        base_round = engine_pps(per_model, 1, max_batch)
        new_round = engine_pps(fused, depth, max_batch)
        base_pps = mega_pps = 0.0
        for _ in range(4):                      # interleaved best-of
            base_pps = max(base_pps, base_round())
            mega_pps = max(mega_pps, new_round())
        serve_rows.append({
            "max_batch": max_batch,
            "depth": depth,
            "pr4_sync_pps": round(base_pps),
            "fused_overlap_pps": round(mega_pps),
            "speedup": round(mega_pps / base_pps, 2),
        })

    print("\n== serving path: megakernel + overlap engine vs PR-4 "
          "per-model sync engine (pkt/s) ==")
    print(render_table(
        serve_rows,
        ["max_batch", "depth", "pr4_sync_pps", "fused_overlap_pps",
         "speedup"],
    ))
    best_serve = max(r["speedup"] for r in serve_rows)
    return {
        "schedule": fused.schedule,
        "rows": rows,
        "serve_rows": serve_rows,
        "max_speedup_direct": best_direct,
        "max_speedup": best_serve,
        "bit_exact_vs_run_dag": True,
    }


def main() -> dict:
    d, node, pipes = build_chain()
    dag = chaining.compile_dag(node, pipes)
    dag_pallas = chaining.compile_dag(node, pipes, backend="pallas")

    ver_eager = chaining.run_dag(node, pipes, d.test_x)
    ver_jit = dag(d.test_x)
    assert np.array_equal(ver_eager, ver_jit), "compiled DAG diverged"
    assert np.array_equal(ver_eager, dag_pallas(d.test_x)), \
        "pallas DAG diverged"

    rows = []
    for n in BATCHES:
        X = d.test_x[:n]
        interp = bench(lambda x: chaining.run_dag(node, pipes, x), X)
        whole = bench(dag, X)
        pallas = bench(dag_pallas, X)
        eng = PacketServeEngine(dag_pallas, feature_dim=d.num_features,
                                max_batch=n)

        def served(x, _e=eng):
            _e.submit(x)
            return _e.flush()

        engine = bench(served, X)
        rows.append({
            "batch": n,
            "interp_pps": round(interp),
            "dagjit_pps": round(whole),
            "pallas_pps": round(pallas),
            "engine_pps": round(engine),
            "dagjit_x": round(whole / interp, 2),    # the PR-1 baseline ratio
            "pallas_x": round(pallas / interp, 2),
        })

    print("\n== whole-DAG JIT vs interpreted chaining (pkt/s) ==")
    print(render_table(
        rows, ["batch", "interp_pps", "dagjit_pps", "pallas_pps",
               "engine_pps", "dagjit_x", "pallas_x"]
    ))

    # the megakernel gate (chained AD > TC, acceptance: >= 1.5x per-model)
    fused_dag = bench_fused_dag(d, pipes)

    # per-pipeline backend gate: the fused-MLP (DNN) pipeline served by the
    # Pallas backend must beat the interpreted stage-apply path
    from repro.core import stageir

    stages = pipes["ad"].stages
    run_interp = stageir.compile_stages(stages, backend="interpret")
    run_pallas = stageir.compile_stages(stages, backend="pallas")
    assert run_pallas.backend == "pallas", "DNN pipeline must lower to pallas"
    X = d.test_x[:BATCHES[-1]]
    assert np.array_equal(np.asarray(run_interp(X)),
                          np.asarray(run_pallas(X))), "pallas diverged"
    backend_rows = []
    for n in BATCHES:
        Xn = d.test_x[:n]
        ipps = bench(lambda x: np.asarray(run_interp(x)), Xn)
        ppps = bench(lambda x: np.asarray(run_pallas(x)), Xn)
        backend_rows.append({
            "batch": n,
            "interp_pps": round(ipps),
            "pallas_pps": round(ppps),
            "speedup": round(ppps / ipps, 2),
        })
    print("\n== fused-MLP pipeline: interpreter vs Pallas backend (pkt/s) ==")
    print(render_table(
        backend_rows, ["batch", "interp_pps", "pallas_pps", "speedup"]
    ))
    best = max(r["speedup"] for r in backend_rows)

    # serve-engine stats per engine x backend for BENCH_serve.json
    ad_tc = _leaf("ad") > _leaf("tc")
    serve_stats = [
        _serve_stat(chaining.compile_dag(ad_tc, pipes), d,
                    label="ad>tc"),
        _serve_stat(chaining.compile_dag(ad_tc, pipes, backend="pallas",
                                         fuse_dag=False), d,
                    label="ad>tc"),
        _serve_stat(chaining.compile_dag(ad_tc, pipes, backend="pallas"), d,
                    label="ad>tc"),
    ]
    sharded = _sharded_serve_stat()
    if sharded is not None:
        serve_stats.append(sharded)
    print("\n== serving-engine stats (BENCH_serve entries) ==")
    print(render_table(
        serve_stats,
        ["engine", "pipeline", "backend", "depth", "shards", "pkt_per_s",
         "lat_p50_ms", "lat_p95_ms", "lat_p99_ms"],
    ))

    payload = {
        "schedule": dag.schedule,
        "verdicts_match": True,
        "model_backends": dag_pallas.model_backends,
        "rows": rows,
        "fused_dag": fused_dag,
        "backend_rows": backend_rows,
        # same definition as the PR-1 baseline: whole-DAG jit vs interpreted
        "max_speedup": max(r["dagjit_x"] for r in rows),
        "pallas_vs_interp_max_speedup": best,
        "fused_dag_vs_permodel_max_speedup": fused_dag["max_speedup"],
        "serve_stats": serve_stats,
    }
    save_result("dag_throughput", payload)

    # timing gates LAST, after the artifact records the measured numbers
    # — a flaky shared-runner measurement must fail the gate, not erase
    # the trajectory entry
    assert fused_dag["max_speedup_direct"] >= 1.0, (
        f"fused-DAG megakernel slower than per-model launches at every "
        f"batch size ({fused_dag['max_speedup_direct']}x)"
    )
    assert best >= 1.0, (
        f"Pallas backend slower than the interpreter on the fused-MLP "
        f"pipeline ({best}x)"
    )
    assert fused_dag["max_speedup"] >= FUSED_DAG_GATE, (
        f"fused-DAG serving path only {fused_dag['max_speedup']}x the "
        f"PR-4 per-model-launch baseline (gate {FUSED_DAG_GATE}x)"
    )
    return payload


if __name__ == "__main__":
    main()
