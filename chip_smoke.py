"""Serve the main path once on a TPU and check what comes out.

  python chip_smoke.py             # one chip: stateful + stateless paths
  python chip_smoke.py --chips 4   # four chips: the sharded engine only

One chip:

  (a) stateful — the flow-ddos pipeline (per-flow registers + a DNN
      trained from a seed, ``benchmarks/flow_throughput.build_pipeline``)
      and the mitigated MAT pipeline (``build_mat_pipeline``), with
      ``MAX_SLOTS``-slot tables, through ``StatefulPipeline(backend=
      "pallas")`` and ``PacketServeEngine(max_batch=512)`` on a seeded
      ``ddos_burst`` stream that occupies at least a quarter of the
      slots.  Both must serve as the one fused kernel launch
      (``pallas-fused-flow``); verdicts and the final tables must equal,
      bit for bit, those of the ``backend="interpret"`` engine on the
      same chip.  The stream is served in windows, each ending in a flush
      (a flow-table health scan of the donated live tables), and the
      flow-ddos engine hot-swaps a fresh copy of its pipeline half way.
  (b) stateless — the ``ad`` anomaly-detection MLP through
      ``PacketServeEngine(backend="pallas")``, checked the same way.

Four chips: ``ShardedPacketServeEngine`` serving the fused flow-ddos
pipeline against one single-device reference engine per shard, and the
stateless ``ad`` split against the one-device engine.

Every Pallas step must compile to Mosaic kernels (``tpu_custom_call``):
nothing may run in interpret mode or through a reference path.  The last
line of standard output is one JSON object; with no TPU, or when any
check fails, the script exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

BATCH = 512
WINDOW = 1 << 16               # packets per submit + flush
FEATURES = 4                   # traffic.COLUMNS
AD_FEATURES = 7


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("smoke:", *parts, flush=True)


def mosaic_kernels(compiled) -> int:
    """Mosaic kernel launches in a compiled executable."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def report_memory(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    say(f"{name} memory_analysis: argument={m.argument_size_in_bytes} "
        f"output={m.output_size_in_bytes} alias={m.alias_size_in_bytes} "
        f"temp={m.temp_size_in_bytes} "
        f"generated_code={m.generated_code_size_in_bytes} bytes")


def serve(eng, packets, *, swap_to=None):
    """Serve ``packets`` in windows, each ending in a flush; with
    ``swap_to``, hot-swap that pipeline in after the middle window."""
    import numpy as np

    out = []
    starts = range(0, len(packets), WINDOW)
    for i, s in enumerate(starts):
        eng.submit(packets[s:s + WINDOW])
        out.append(eng.flush())
        if swap_to is not None and i == len(starts) // 2:
            eng.swap(swap_to)
    return np.concatenate(out)


def state_arrays(pipe, state) -> list:
    import numpy as np

    return [np.asarray(a) for a in pipe._state_arrays(state)]


def compile_fused_step(name, pipe, state):
    """AOT-compile the serving step the engine runs; report it."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    compiled = pipe._step.lower(
        *pipe._state_arrays(state),
        jnp.zeros((BATCH, FEATURES), jnp.float32),
        jnp.zeros((BATCH,), jnp.int32)).compile()
    say(f"{name} step compile_s={time.perf_counter() - t0:.3f}")
    report_memory(f"{name} step", compiled)
    n = mosaic_kernels(compiled)
    check(n == 1, f"{name}: expected one Mosaic launch per step, got {n}")


def make_stream(n_slots: int, seed: int):
    """A seeded ddos_burst stream whose benign flows occupy at least a
    quarter of an ``n_slots`` table: many short-lived benign flows over a
    half-second span, cut at 1.5 packets per slot."""
    from repro.data import traffic

    return traffic.make_stream("ddos_burst", n_packets=n_slots * 3 // 2,
                               n_benign_flows=n_slots * 2 // 5,
                               span_s=0.5, seed=seed)


def stateful_phase(n_slots: int, seed: int) -> None:
    import numpy as np

    from benchmarks.flow_throughput import build_mat_pipeline, build_pipeline
    from repro.flowstate import MITIGATED, StatefulPipeline
    from repro.serve.packet_engine import PacketServeEngine

    t0 = time.perf_counter()
    stream = make_stream(n_slots, seed + 1)
    say(f"stream ddos_burst packets={stream.n_packets} "
        f"flows={len(np.unique(stream.flow_ids))} "
        f"gen_s={time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    pipelines = {"flow-ddos": build_pipeline(n_slots=n_slots),
                 "mitigate-mat": build_mat_pipeline(mitigated=True,
                                                    n_slots=n_slots)}
    say(f"pipelines built (DNN trained from seed 0) "
        f"s={time.perf_counter() - t0:.1f}")

    for name, stages in pipelines.items():
        res = {}
        for backend in ("interpret", "pallas"):
            pipe = StatefulPipeline(stages, backend=backend)
            swap_to = None
            if backend == "pallas":
                check(pipe.backend == "pallas-fused-flow",
                      f"{name}: fused launch declined "
                      f"({pipe.fallback_reason})")
                compile_fused_step(name, pipe, pipe.init_state())
                if name == "flow-ddos":
                    swap_to = StatefulPipeline(stages, backend="pallas")
            t0 = time.perf_counter()
            eng = PacketServeEngine(pipe, feature_dim=FEATURES,
                                    max_batch=BATCH)
            warm = time.perf_counter() - t0
            t0 = time.perf_counter()
            verdicts = serve(eng, stream.packets, swap_to=swap_to)
            wall = time.perf_counter() - t0
            st = eng.stats()
            check(st["backend"] == pipe.backend,
                  f"{name}/{backend}: engine reports {st['backend']}")
            check(set(st["backend_batches"]) == {pipe.backend},
                  f"{name}/{backend}: batches served by "
                  f"{st['backend_batches']}")
            check(st["packets"] == stream.n_packets,
                  f"{name}/{backend}: served {st['packets']} packets")
            if swap_to is not None:
                check(st["swaps"] == 1, f"{name}: swap not installed")
            occupied = eng.telemetry().metrics.get(
                "flow_occupied_slots").value()
            res[backend] = (verdicts, state_arrays(eng.pipeline, eng.state),
                            occupied)
            say(f"{name} backend={st['backend']} packets={st['packets']} "
                f"batches={st['batches']} swaps={st['swaps']} "
                f"warmup_s={warm:.3f} serve_wall_s={wall:.3f}")
            del eng

        (v_ref, s_ref, _), (v, s, occupied) = res["interpret"], res["pallas"]
        check(np.array_equal(v, v_ref), f"{name}: verdicts differ from the "
              f"interpret engine at {int(np.sum(v != v_ref))} packets")
        for i, (a, b) in enumerate(zip(s, s_ref)):
            check(a.dtype == b.dtype and np.array_equal(a, b),
                  f"{name}: final state array {i} differs")
        keys = s[0]
        occ = int(np.sum(keys >= 0))
        check(occ == occupied, f"{name}: health scan saw {occupied} "
              f"occupied slots, the table holds {occ}")
        check(occ * 4 >= n_slots, f"{name}: only {occ} of {n_slots} "
              f"slots occupied")
        say(f"{name} table slots={n_slots} occupied={occ} "
            f"({occ / n_slots:.4f}) mitigated_pkts="
            f"{int(np.sum(v == MITIGATED))} attack_verdicts="
            f"{int(np.sum(v == 1))}: verdicts, keys and registers equal "
            f"the interpret engine's")


def ad_pipeline(seed: int, n_test: int):
    from repro.core import codegen, feasibility, mlalgos
    from repro.data import netdata

    d = netdata.make_ad_dataset(features=AD_FEATURES, n_train=4096,
                                n_test=n_test, seed=seed)
    rep = feasibility.FeasibilityReport(True, [], {"cu": 1}, 1.0, 1e9)
    dnn = mlalgos.train_dnn(d, hidden=[16, 8], epochs=3, seed=seed)
    return codegen.taurus_codegen("ad", dnn, rep), d.test_x


def stateless_phase(seed: int, n_test: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import stageir
    from repro.serve.packet_engine import PacketServeEngine

    pipe, X = ad_pipeline(seed, n_test)
    compiled = stageir.compile_stages(pipe.stages, backend="pallas")
    check(compiled.backend == "pallas",
          f"ad: Pallas lowering declined ({compiled.backend})")
    t0 = time.perf_counter()
    exe = compiled.fn.lower(jnp.zeros((BATCH, AD_FEATURES),
                                      jnp.float32)).compile()
    say(f"ad step compile_s={time.perf_counter() - t0:.3f}")
    report_memory("ad step", exe)
    check(mosaic_kernels(exe) >= 1, "ad: no Mosaic kernel in the step")

    out = {}
    for backend in ("interpret", "pallas"):
        eng = PacketServeEngine(pipe, feature_dim=AD_FEATURES,
                                max_batch=BATCH, backend=backend)
        out[backend] = serve(eng, X)
        st = eng.stats()
        check(st["backend"] == backend,
              f"ad: {backend} engine reports {st['backend']}")
        say(f"ad backend={st['backend']} packets={st['packets']} "
            f"batches={st['batches']}")
    check(np.array_equal(out["pallas"], out["interpret"]),
          "ad: verdicts differ from the interpret engine")
    say(f"ad verdicts equal the interpret engine's "
        f"(flagged {int(np.sum(out['pallas'] == 1))} of {len(X)})")


def sharded_phase(n_slots: int, seed: int, n_test: int) -> None:
    """Four chips: the sharded engine vs what it must equal."""
    import jax
    import numpy as np

    from benchmarks.flow_throughput import build_pipeline
    from repro.core import stageir
    from repro.flowstate import StatefulPipeline
    from repro.serve import ShardedPacketServeEngine
    from repro.serve.packet_engine import PacketServeEngine
    from repro.serve.sharded import shard_of_key

    n_dev = len(jax.devices())
    stages = build_pipeline(n_slots=n_slots)
    stream = make_stream(n_slots, seed + 1)
    X = stream.packets
    pipe = StatefulPipeline(stages, backend="pallas")
    check(pipe.backend == "pallas-fused-flow",
          f"flow-ddos: fused launch declined ({pipe.fallback_reason})")
    sh = ShardedPacketServeEngine(pipe, feature_dim=FEATURES,
                                  max_batch=BATCH)
    check(sh.sharded and sh.n_shards == n_dev,
          f"sharded engine degraded ({sh.n_shards} shards)")
    t0 = time.perf_counter()
    v = serve(sh, X)
    st = sh.stats()
    check(st["backend"] == "pallas-fused-flow" and st["shards"] == n_dev,
          f"sharded engine reports {st['backend']} x {st['shards']}")
    say(f"sharded flow-ddos backend={st['backend']} shards={st['shards']} "
        f"packets={st['packets']} serve_wall_s="
        f"{time.perf_counter() - t0:.3f}")

    fk = next(s for s in stages if isinstance(s, stageir.FlowKey))
    ids = shard_of_key(fk.apply_keys_np(X), n_dev)
    keys, regs = np.asarray(sh.state.keys), np.asarray(sh.state.regs)
    for s in range(n_dev):
        ref = PacketServeEngine(StatefulPipeline(stages, backend="interpret"),
                                feature_dim=FEATURES, max_batch=BATCH)
        rv = serve(ref, X[ids == s])
        check(np.array_equal(v[ids == s], rv),
              f"shard {s}: verdicts differ from its reference engine")
        check(np.array_equal(keys[s], np.asarray(ref.state.keys))
              and np.array_equal(regs[s], np.asarray(ref.state.regs)),
              f"shard {s}: final table differs from its reference engine")
        say(f"shard {s}: packets={int(np.sum(ids == s))} occupied="
            f"{int(np.sum(keys[s] >= 0))} of {n_slots}: verdicts and "
            f"table equal the single-device interpret engine's")

    ad, Xa = ad_pipeline(seed, n_test)
    base = PacketServeEngine(ad, feature_dim=AD_FEATURES, max_batch=BATCH,
                             backend="pallas")
    sha = ShardedPacketServeEngine(ad, feature_dim=AD_FEATURES,
                                   max_batch=BATCH, backend="pallas")
    check(sha.sharded and sha.stats()["shards"] == n_dev,
          "stateless sharded engine degraded")
    check(np.array_equal(serve(sha, Xa), serve(base, Xa)),
          "ad: sharded verdicts differ from the one-device engine")
    say(f"sharded ad backend={sha.stats()['backend']} shards={n_dev} "
        f"packets={len(Xa)}: verdicts equal the one-device engine's")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-engine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.flow_update import MAX_SLOTS

    say(f"device kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(MAX_SLOTS // 4, args.seed, n_test=1 << 14)
    else:
        say(f"flow table slots={MAX_SLOTS} (MAX_SLOTS)")
        stateful_phase(MAX_SLOTS, args.seed)
        stateless_phase(args.seed, n_test=1 << 16)
    say(f"all checks passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
