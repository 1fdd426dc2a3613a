"""Benchmark harness: one entry per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table2 fig7  # a subset

After the selected benches run, the per-engine serving stats recorded by
``dag_throughput`` / ``flow_throughput`` are consolidated into
``benchmarks/results/BENCH_serve.json`` — the machine-readable perf
trajectory (pkt/s + p50/p95/p99 latency per engine x backend) future PRs
diff throughput against.  ``ShardedPacketServeEngine`` rows run on four
devices (``common.sharded_stats``: in-process on a four-chip host,
forced host devices in a CPU child otherwise), so their ``shards`` field
records the actual device count of the run.  A failure to consolidate
fails the run.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

from benchmarks import (
    attack_defense,
    dag_throughput,
    dryrun_roofline,
    dse_throughput,
    fig4_regret,
    flow_throughput,
    fig6_reaction_time,
    fig7_kmeans_mats,
    hot_swap,
    kernel_roofline,
    table2_f1,
    table3_chaining,
    table4_fusion,
    table5_resources,
    telemetry_overhead,
)

BENCHES = {
    "table2": ("Table 2: baselines vs generated F1/resources", table2_f1.main),
    "table3": ("Table 3: chaining strategies", table3_chaining.main),
    "table4": ("Table 4: model fusion", table4_fusion.main),
    "table5": ("Table 5: FPGA resources", table5_resources.main),
    "fig4": ("Figure 4: BO regret", fig4_regret.main),
    "fig6": ("Figure 6: reaction time", fig6_reaction_time.main),
    "fig7": ("Figure 7: KMeans vs MATs", fig7_kmeans_mats.main),
    "dag": ("whole-DAG JIT vs interpreted chaining pkt/s",
            dag_throughput.main),
    "dse": ("sequential vs batched DSE candidates/sec",
            dse_throughput.main),
    "flow": ("stateful flow pipeline: interpreter vs fused launch pkt/s",
             flow_throughput.main),
    "attack": ("closed-loop attack/defense replay with SLO gates",
               attack_defense.main),
    "swap": ("hot-swap latency + post-drift F1 recovery", hot_swap.main),
    "kernel": ("fused_mlp kernel roofline + stateful step",
               kernel_roofline.main),
    "telemetry": ("telemetry plane overhead: pkt/s on vs off",
                  telemetry_overhead.main),
    "dryrun": ("dry-run roofline summary", dryrun_roofline.main),
}


# benches whose saved results carry "serve_stats" entries
_SERVE_SOURCES = ("dag_throughput", "flow_throughput", "hot_swap",
                  "attack_defense", "telemetry_overhead")


def write_bench_serve() -> str | None:
    """Consolidate serve_stats from the source benches' saved results into
    benchmarks/results/BENCH_serve.json; returns the path (None when no
    source results exist yet)."""
    from benchmarks.common import RESULTS_DIR, save_result

    entries = []
    for name in _SERVE_SOURCES:
        path = os.path.join(RESULTS_DIR, f"{name}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            payload = json.load(f)
        for e in payload.get("serve_stats", []):
            entries.append({"bench": name, **e})
    if not entries:
        return None
    return save_result("BENCH_serve", {
        "description": "pkt/s + latency percentiles per serving engine x "
                       "execution backend (consolidated perf trajectory)",
        "entries": entries,
    })


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = sys.argv[1:] or list(BENCHES)
    summary = []
    for name in names:
        desc, fn = BENCHES[name]
        print(f"\n{'=' * 72}\n[{name}] {desc}\n{'=' * 72}", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            status = "ok"
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            status = f"FAIL {type(e).__name__}: {e}"
        summary.append((name, status, time.perf_counter() - t0))

    # ALWAYS consolidate, even when benches failed their gates: every
    # bench saves its artifact BEFORE asserting, so the trajectory
    # refreshes from whatever measurements exist
    t0 = time.perf_counter()
    try:
        serve_path = write_bench_serve()
        status = "ok"
    except Exception as e:  # noqa: BLE001 — reported, and fails the run
        traceback.print_exc()
        serve_path = None
        status = f"FAIL {type(e).__name__}: {e}"
    summary.append(("BENCH_serve", status, time.perf_counter() - t0))
    if serve_path:
        print(f"\nconsolidated serving stats -> {serve_path}")

    print(f"\n{'=' * 72}\nbenchmark summary\n{'=' * 72}")
    print("name,status,wall_s")
    failed = 0
    for name, status, wall in summary:
        print(f"{name},{status},{wall:.1f}")
        failed += status != "ok"
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
