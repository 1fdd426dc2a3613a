"""Closed-loop attack/defense replay harness with SLO gates.

For each flood scenario: train a detector on one seeded stream, attach an
in-pipeline ``Mitigate`` stage (per-flow drop registers), replay a
DIFFERENT seed of the same scenario through ``PacketServeEngine`` on both
execution engines, and gate on what the data plane ENFORCES:

  * median packets-to-first-DROP per attack flow (detection reaction +
    mitigation lag) <= the scenario's SLO;
  * ZERO attack packets leaked after a flow's first drop (drop mode is
    leak-free by construction — this gate catches any regression in the
    action-table carry across batches, overlap depth, or engines);
  * detection rate >= 0.9, benign collateral damage bounded.

The replay is fully deterministic (seeded streams, seeded training, CPU
math), so these are structural gates, not timing gates — they run hard
in CI.  The same mitigated pipeline serves through
``ShardedPacketServeEngine`` on four devices for the shards > 1 row
(``common.sharded_stats``), and a
rate-limit run hot-swaps the detector MID-mitigation to pin the
swap-while-limited contract.  All rows consolidate into BENCH_serve.json
via benchmarks.run.

  PYTHONPATH=src python -m benchmarks.attack_defense
"""

from __future__ import annotations

import os

import numpy as np

from repro.core import codegen, mlalgos, stageir
from repro.data import traffic
from repro.flowstate import MITIGATED, MitigationSpec, StatefulPipeline
from repro.serve.packet_engine import PacketServeEngine
from repro.telemetry import Telemetry

from benchmarks.common import (
    RESULTS_DIR,
    render_table,
    save_result,
    sharded_stats,
)

# the operator event journal of the replay (drift/swap/mitigation/SLO
# events, JSON lines) — CI uploads this file as a build artifact
JOURNAL_PATH = os.path.join(RESULTS_DIR, "attack_defense_journal.jsonl")

N_PACKETS = 12_000
N_SLOTS = 2048          # detection table
MIT_SLOTS = 4096        # action table (generous: collisions would evict
                        # marked flows and show up as leaked packets)
THRESHOLD = 8
BATCH = 512
TRAIN_SEED, REPLAY_SEED = 0, 1

SCENARIOS = ("syn_flood", "udp_flood", "coordinated_ddos")

# median packets until the data plane STOPS an attack flow (detection
# reaction + mitigation lag), per scenario
SLO_REACTION_PKTS = {"syn_flood": 64, "udp_flood": 64,
                     "coordinated_ddos": 96}
SLO_DETECTION_RATE = 0.9
SLO_BENIGN_MITIGATED = 0.25


def build_pipeline(scenario: str, *, mode: str = "drop",
                   keep_every: int = 4):
    """Train the scenario's detector and cap it with a Mitigate stage."""
    train = traffic.make_stream(scenario, n_packets=N_PACKETS,
                                seed=TRAIN_SEED)
    stages, names = traffic.flow_feature_stages(n_slots=N_SLOTS)
    ds, mu, sd = traffic.stream_feature_dataset(train, stages, names,
                                                sample_every=4)
    dnn = mlalgos.train_dnn(ds, hidden=[16, 8], epochs=3, seed=0)
    suffix = traffic.fold_input_standardization(
        codegen.taurus_stages(dnn), mu, sd)
    mit = stageir.Mitigate(MitigationSpec(
        n_slots=MIT_SLOTS, mode=mode, threshold=THRESHOLD,
        keep_every=keep_every))
    return list(stages) + suffix + [mit]


def serve_once(pipe, stream, *, depth: int = 2, telemetry=False):
    eng = PacketServeEngine(pipe, feature_dim=len(traffic.COLUMNS),
                            max_batch=BATCH, depth=depth,
                            telemetry=telemetry)
    v = np.concatenate(list(eng.serve_stream(stream.chunks(BATCH))))
    return v, eng


def sharded_run() -> dict:
    """The mitigated syn_flood replay through ``ShardedPacketServeEngine``
    on four devices -> its stats; the action tables must drop packets."""
    import jax

    from repro.serve import ShardedPacketServeEngine

    assert len(jax.devices()) == 4, jax.devices()
    pipe = StatefulPipeline(build_pipeline("syn_flood"), backend="pallas")
    assert pipe.backend == "pallas-fused-flow", pipe.backend
    stream = traffic.make_stream("syn_flood", n_packets=N_PACKETS,
                                 seed=REPLAY_SEED)
    eng = ShardedPacketServeEngine(pipe, feature_dim=len(traffic.COLUMNS),
                                   max_batch=BATCH)
    assert eng.sharded and eng.n_shards == 4, (eng.sharded, eng.n_shards)
    for _ in range(2):
        dropped = 0
        for v in eng.serve_stream(stream.chunks(BATCH)):
            dropped += int((v == MITIGATED).sum())
    assert dropped > 0, "sharded replay mitigated nothing"
    assert int(eng.state.mitigated_flows) > 0
    return eng.stats()


def _stat_row(stats: dict, pipeline: str, engine: str) -> dict:
    return {
        "engine": engine,
        "pipeline": pipeline,
        "backend": stats["backend"],
        "depth": stats["depth"],
        "shards": stats["shards"],
        "pkt_per_s": stats["pkt_per_s"],
        "lat_p50_ms": stats["lat_p50_ms"],
        "lat_p95_ms": stats["lat_p95_ms"],
        "lat_p99_ms": stats["lat_p99_ms"],
    }


def swap_under_rate_limit() -> dict:
    """Hot-swap the detector while flows are actively rate-limited; the
    action table must carry bit-identically (same verdict stream as the
    unswapped run) and the swap must count exactly once."""
    stages = build_pipeline("syn_flood", mode="rate_limit")
    stream = traffic.make_stream("syn_flood", n_packets=N_PACKETS,
                                 seed=REPLAY_SEED)
    chunks = list(stream.chunks(BATCH))

    pipe = StatefulPipeline(stages, backend="pallas")
    assert pipe.backend == "pallas-fused-flow", (
        f"rate-limit pipeline outside the fused envelope: {pipe.backend!r} "
        f"(reason: {pipe.fallback_reason})")
    ref, _ = serve_once(pipe, stream)

    eng = PacketServeEngine(StatefulPipeline(stages, backend="pallas"),
                            feature_dim=len(traffic.COLUMNS),
                            max_batch=BATCH, depth=2)
    got = []
    for i, c in enumerate(chunks):
        if i == len(chunks) // 2:
            assert int(eng.state.mitigated_flows) > 0, \
                "swap must land while flows are being rate-limited"
            eng.swap(StatefulPipeline(stages, backend="pallas"))
        eng.submit(c)
        got.append(eng.flush())
    v = np.concatenate(got)
    np.testing.assert_array_equal(
        v, ref, err_msg="hot swap perturbed the mitigation stream")
    assert eng.stats()["swaps"] == 1
    return {
        "dropped_pkts": int((v == MITIGATED).sum()),
        "mitigated_flows": int(eng.state.mitigated_flows),
        "swap_lat_ms": eng.stats()["swap_lat_ms"],
    }


def main() -> dict:
    # ONE shared telemetry plane for the whole replay: every scenario's
    # pallas engine reports into it, and its journal (mitigation
    # engagements, SLO-gate outcomes) lands in the JSON-lines artifact
    os.makedirs(RESULTS_DIR, exist_ok=True)
    if os.path.exists(JOURNAL_PATH):
        os.remove(JOURNAL_PATH)
    tel = Telemetry(journal_path=JOURNAL_PATH)

    rows, serve_stats, reports, gates = [], [], {}, []
    for scenario in SCENARIOS:
        stages = build_pipeline(scenario)
        stream = traffic.make_stream(scenario, n_packets=N_PACKETS,
                                     seed=REPLAY_SEED)
        verdicts, engines = {}, {}
        for backend in ("interpret", "pallas"):
            pipe = StatefulPipeline(stages, backend=backend)
            if backend == "pallas":
                # the action table folds into the fused launch: the whole
                # mitigated chain must serve as ONE kernel
                assert pipe.backend == "pallas-fused-flow", (
                    f"{scenario}: expected the fused launch, got "
                    f"{pipe.backend!r} (reason: {pipe.fallback_reason})")
            verdicts[backend], engines[backend] = serve_once(
                pipe, stream,
                telemetry=tel if backend == "pallas" else False)
        np.testing.assert_array_equal(
            verdicts["interpret"], verdicts["pallas"],
            err_msg=f"{scenario}: engines diverged under mitigation")
        for f in ("mit_keys", "mit_regs"):
            np.testing.assert_array_equal(
                np.asarray(getattr(engines["interpret"].state, f)),
                np.asarray(getattr(engines["pallas"].state, f)),
                err_msg=f"{scenario}: final {f} diverged")

        react = traffic.reaction_report(stream, verdicts["pallas"])
        reports[scenario] = react
        stop_median = (react["reaction_pkts_median"]
                       + react["mitigation_lag_median"])
        rows.append({
            "scenario": scenario,
            "attack_flows": react["attack_flows"],
            "detect_rate": round(react["detection_rate"], 3),
            "stop_median_pkts": stop_median,
            "slo_pkts": SLO_REACTION_PKTS[scenario],
            "leaked": react["leaked_pkts_total"],
            "benign_hit": round(react["benign_mitigated_flow_rate"], 3),
        })
        serve_stats.append(_stat_row(engines["pallas"].stats(),
                                     f"mitigate-{scenario}",
                                     "PacketServeEngine"))
        gates.append((scenario, react, stop_median))

    print("\n== closed-loop replay: packets until the data plane stops an "
          "attack flow ==")
    print(render_table(rows, ["scenario", "attack_flows", "detect_rate",
                              "stop_median_pkts", "slo_pkts", "leaked",
                              "benign_hit"]))

    swap = swap_under_rate_limit()
    print(f"\nswap under rate-limit: {swap}")

    sharded = sharded_stats("benchmarks.attack_defense", "sharded_run")
    if sharded is not None:
        assert sharded["shards"] > 1, \
            f"sharded run degraded to {sharded['shards']} shard"
        serve_stats.append(_stat_row(sharded, "mitigate-syn_flood",
                                     "ShardedPacketServeEngine"))

    print("\n== serving-engine stats (BENCH_serve entries) ==")
    print(render_table(
        serve_stats,
        ["engine", "pipeline", "backend", "depth", "shards", "pkt_per_s",
         "lat_p50_ms", "lat_p95_ms", "lat_p99_ms"]))

    # journal every SLO outcome FIRST — a violated gate must show up in
    # the uploaded artifact, not vanish with the raised assert
    outcomes = []
    for scenario, react, stop_median in gates:
        slo = SLO_REACTION_PKTS[scenario]
        checks = {
            "detection_rate": bool(react["detection_rate"]
                                   >= SLO_DETECTION_RATE),
            "stop_median_pkts": bool(stop_median <= slo),
            "leaked_pkts": react["leaked_pkts_total"] == 0,
            "benign_collateral": bool(react["benign_mitigated_flow_rate"]
                                      <= SLO_BENIGN_MITIGATED),
        }
        tel.journal.emit(
            "slo_gate", scenario=scenario, ok=all(checks.values()),
            checks=checks,
            detection_rate=round(react["detection_rate"], 4),
            stop_median_pkts=stop_median, slo_pkts=slo,
            leaked_pkts=react["leaked_pkts_total"],
            benign_rate=round(react["benign_mitigated_flow_rate"], 4))
        outcomes.append((scenario, react, stop_median, slo, checks))

    payload = {
        "n_packets": N_PACKETS,
        "mit_slots": MIT_SLOTS,
        "threshold": THRESHOLD,
        "slo_reaction_pkts": SLO_REACTION_PKTS,
        "reports": reports,
        "swap_under_rate_limit": swap,
        "serve_stats": serve_stats,
        "journal_path": JOURNAL_PATH,
        "journal_events": len(tel.journal.events()),
    }
    save_result("attack_defense", payload)
    tel.close()
    print(f"\noperator event journal -> {JOURNAL_PATH} "
          f"({payload['journal_events']} events)")

    # SLO gates LAST, after the artifact records the measured numbers —
    # a violated SLO must fail the gate, not erase the trajectory entry
    for scenario, react, stop_median, slo, checks in outcomes:
        assert checks["detection_rate"], (
            f"{scenario}: detection rate {react['detection_rate']:.3f} "
            f"below {SLO_DETECTION_RATE}")
        assert checks["stop_median_pkts"], (
            f"{scenario}: median packets-to-stop {stop_median} exceeds "
            f"the {slo}-packet SLO")
        assert checks["leaked_pkts"], (
            f"{scenario}: {react['leaked_pkts_total']} attack packets "
            f"leaked past installed drop entries")
        assert checks["benign_collateral"], (
            f"{scenario}: benign collateral "
            f"{react['benign_mitigated_flow_rate']:.3f} above "
            f"{SLO_BENIGN_MITIGATED}")
    return payload


if __name__ == "__main__":
    main()
