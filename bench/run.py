#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  A run sets up (builds the configuration's pipeline and its
engine, compiles the one batch shape, makes one lap of traffic from the
seed and serves the mix's warm-up packets), then drives the engine for
``--seconds`` (``bench/window.py``), then checks a sample of what it
served against the plain reference (``bench/check.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler records a stretch of the window and the metrics
are the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` [, ``breakdown``],
``checks``); the compared numbers and their limits are also the last lines
of standard error.  Without an accelerator, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

CACHE_DIR = ROOT / "bench" / ".cache" / "jax"
WORK_DIR = ROOT / "bench" / ".work"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is kept, however quickly it compiled."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int, require_accelerator: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_accelerator and devices[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


class CompileCounter:
    """Programs lowered while ``armed`` (each is a compile or a cache
    load): the window must see none."""

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k) -> None:
        if self.armed and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


@dataclasses.dataclass
class Served:
    """One window served and what the check and the metrics read."""

    window: object             # window.Result
    warm: int                  # packets served before the window
    verdicts: np.ndarray       # every verdict, warm-up included
    sample: object             # check.Sample
    served_table: dict         # the program's final rows of the sample
    memory_peak: int
    window_compiles: int
    setup_s: float


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def serve(cell, built, seed: int, seconds: float, devices, t_start: float,
          tracer=None, counter: CompileCounter | None = None) -> Served:
    """Set up, warm up, serve the window, fetch the sample: the timed path
    and what ``correct`` compares, with the program's state freed at the
    end."""
    import numpy as np

    from bench import check, window
    from bench.traffic import generator

    mix = cell.mix
    if mix["arrival"] != {"mode": "backlogged"}:
        raise ValueError(f"arrival {mix['arrival']}: only backlogged "
                         "arrivals are served")
    t = time.perf_counter()
    lap = generator.make_lap(mix, seed)
    say(f"lap of {lap.size} packets in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    eng = built.engine(len(devices))
    say(f"engine built and step compiled in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    warm = int(mix["warm_packets"])
    eng.submit(lap.take(0, warm))
    warm_verdicts = eng.flush()
    say(f"{warm} warm-up packets in {time.perf_counter() - t:.2f}s")
    if tracer is not None:        # the profiler's first start is slow
        import jax

        tracer.start()
        jax.profiler.stop_trace()
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    if counter is not None:
        counter.armed = True
    res = window.run(eng, lap, warm, seconds, int(mix["chunk"]),
                     tracer=tracer)
    compiles = counter.count if counter is not None else 0
    if counter is not None:
        counter.armed = False
    peak = memory_peak(devices)
    n_served = warm + len(res.verdicts)
    verdicts = np.concatenate([warm_verdicts, res.verdicts])
    sample = check.draw_sample(lap, n_served, built.config, len(devices),
                               seed)
    table = check.fetch_rows(eng.state, np.unique(sample.groups),
                             int(built.config["n_slots"]), len(devices))
    del eng
    gc.collect()
    return Served(res, warm, verdicts, sample, table, peak, compiles,
                  setup_s)


def judge(served: Served, built) -> tuple:
    from bench import check

    ref = check.expected(served.sample, built)
    values = check.numbers(served.sample, ref,
                           served.verdicts[served.sample.index],
                           served.served_table, built)
    values["unanswered"] = served.window.submitted - len(
        served.window.verdicts)
    values["non_fused_batches"] = check.non_fused(
        served.window.stats_close.get("backend_counts", {}))
    values["window_compiles"] = served.window_compiles
    return check.judge(values, built.config["check"]["limits"])


@dataclasses.dataclass
class Context:
    """What a metric reader gets."""

    config: dict
    cell: object
    served: Served
    device_kind: str
    n_devices: int
    reduced: object = None     # trace.Reduced of the traced stretch
    stretch_s: float = 0.0
    stretch_packets: int = 0


def read_metrics(entries: list, ctx: Context) -> dict:
    from bench import spec

    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def reduce_trace(tracer, served: Served):
    import numpy as np

    from bench import trace

    path = trace.find_xplane(tracer.out_dir)
    if path is None:
        return None, 0.0, 0
    lo, hi = served.window.trace_stretch
    t = trace.load(path)
    red = trace.reduce(t)
    recv = served.window.recv
    n = int(np.sum((recv >= lo) & (recv <= hi)))
    return red, hi - lo, n


def run_cell(cell, *, seed: int, seconds: float, traced: bool,
             devices, t_start: float) -> dict:
    from bench import pipelines, trace, window

    built = pipelines.Built(cell.config)
    counter = CompileCounter()
    tracer = None
    if traced:
        out = WORK_DIR / "trace" / cell.name
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        tracer = window.Tracer(str(out), lead=min(1.0, seconds * 0.2),
                               stretch=min(2.0, seconds * 0.4))
    served = serve(cell, built, seed, seconds, devices, t_start,
                   tracer=tracer, counter=counter)
    say(f"served {len(served.verdicts)} packets ({served.warm} warm-up), "
        f"window {served.window.submitted} submitted, "
        f"{served.window.answered_in_window} answered in {seconds}s; "
        f"sample {len(served.sample.index)} packets in "
        f"{served.sample.n_groups} rows (deepest {served.sample.deepest})")
    t_ref = time.perf_counter()
    correct, checks = judge(served, built)
    say(f"reference check {time.perf_counter() - t_ref:.1f}s")
    dev = devices[0]
    ctx = Context(cell.config, cell, served, dev.device_kind, len(devices))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": served.memory_peak}
    result = {"correct": bool(correct),
              "attempted": int(served.window.submitted),
              "failed": int(checks["unanswered"]["value"])}
    breakdown = None
    if traced:
        red, stretch_s, n = reduce_trace(tracer, served)
        ctx.reduced, ctx.stretch_s, ctx.stretch_packets = red, stretch_s, n
        metrics = read_metrics(cell.per_layer, ctx)
        if red is not None:
            busy = red.mean(lambda d: d.busy_ns) or 0.0
            device["busy_s"] = busy * 1e-9
            device["window_s"] = stretch_s
            ops: dict = {}
            for d in red.devices.values():
                for k, v in d.op_ns.items():
                    ops[k] = ops.get(k, 0) + v / len(red.devices)
            breakdown = {"device_ops": trace.top(ops),
                         "idle_gaps": trace.top(red.idle_gaps_ns)}
    else:
        metrics = read_metrics(cell.end_to_end, ctx)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec

    cell = spec.cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    say(f"cell {cell.name}: {cell.chips} x {devices[0].device_kind}, "
        f"seed {args.seed}, {args.seconds}s, trace {args.trace}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace), devices=devices,
                      t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
