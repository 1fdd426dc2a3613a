"""Shared benchmark utilities: result table rendering, JSON persistence,
and where the sharded serving rows run (``sharded_stats``)."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# marker line a multi-device probe script prints its stats JSON behind
SHARDED_MARKER = "SHARDED-STATS "


def sharded_stats(module: str, fn: str, *, n_devices: int = 4
                  ) -> dict | None:
    """The stats dict ``module.fn()`` returns — a run of
    ``ShardedPacketServeEngine`` over ``n_devices`` devices — measured
    with one process per chip.

    On an accelerator host the call runs in this process on
    ``jax.devices()`` (a child could not reach a chip this process
    holds); with fewer than ``n_devices`` devices there is no sharded row
    (None).  A CPU parent runs it in a child with ``n_devices`` forced
    host devices (``run_sharded_probe``)."""
    import jax

    if jax.default_backend() != "cpu":
        if len(jax.devices()) < n_devices:
            return None
        return getattr(importlib.import_module(module), fn)()
    return run_sharded_probe(
        f"import json\nfrom {module} import {fn}\n"
        f"print({SHARDED_MARKER!r} + json.dumps({fn}()))",
        n_devices=n_devices)


def run_sharded_probe(script: str, *, n_devices: int = 4,
                      timeout: int = 900) -> dict:
    """Run ``script`` in a CPU-only subprocess with ``n_devices`` forced
    host devices (``--xla_force_host_platform_device_count``, the same
    trick as ``tests/test_sharded_engine.py``) so ``ShardedPacketServeEngine``
    rows in BENCH_serve.json record real multi-device runs — the
    ``shards`` field then carries the actual device count instead of the
    one-device degradation.  ``JAX_PLATFORMS=cpu`` is set explicitly, so
    the child never reaches for a chip.  The script must print one line
    ``SHARDED-STATS {json}``; returns the parsed dict."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO_ROOT,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded probe failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-4000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith(SHARDED_MARKER):
            return json.loads(line[len(SHARDED_MARKER):])
    raise RuntimeError(
        f"sharded probe printed no {SHARDED_MARKER!r} line:\n"
        f"{proc.stdout[-2000:]}"
    )


def save_result(name: str, payload: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return path


def render_table(rows: list[dict], columns: list[str]) -> str:
    widths = {
        c: max(len(c), *(len(str(r.get(c, ""))) for r in rows)) for c in columns
    }
    line = " | ".join(c.ljust(widths[c]) for c in columns)
    sep = "-+-".join("-" * widths[c] for c in columns)
    body = "\n".join(
        " | ".join(str(r.get(c, "")).ljust(widths[c]) for c in columns)
        for r in rows
    )
    return f"{line}\n{sep}\n{body}"


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.wall_s = time.perf_counter() - self.t0


def bench_pps(fn, X, repeats: int = 20) -> float:
    """Measured items/sec of ``fn(X)``: one warm-up call (compile), then
    ``repeats`` timed calls — the shared methodology of the pkt/s benches."""
    fn(X)
    with Timer() as t:
        for _ in range(repeats):
            fn(X)
    return repeats * len(X) / t.wall_s


def bench_pps_best(fn, X, rounds: int = 5, repeats: int = 20) -> float:
    """Best-of-``rounds`` ``bench_pps``: the A/B gates (pallas >= interp,
    fused-DAG >= per-model) compare best-case rates so scheduler noise on
    shared runners doesn't flip a structural speedup into a flake."""
    fn(X)
    return max(bench_pps(fn, X, repeats) for _ in range(rounds))
