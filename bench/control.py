#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: a whole run of the cell as ``run.py``
makes it (set-up, warm-up, the window at the cell's own load, the sample),
then the compared numbers twice: for what the program served, and for the
control, the reference computed in the next precision down (bfloat16
registers, three-pass matmuls) put in the program's place on the same
packets.  One JSON line per seed on standard output.  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import check, pipelines, run, spec

    cell = spec.cell(args.workload)
    try:
        devices = run.chips(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    built = pipelines.Built(cell.config)
    for seed in args.seeds:
        t0 = time.perf_counter()
        served = run.serve(cell, built, seed, args.seconds, devices,
                           time.perf_counter())
        ok, checks = run.judge(served, built)
        ctx = run.Context(cell.config, cell, served, devices[0].device_kind,
                          len(devices))
        metrics = run.read_metrics(cell.end_to_end, ctx)
        t1 = time.perf_counter()
        control = check.control_numbers(served.sample, built)
        ctl_ok, _ = check.judge(control,
                                built.config["check"]["limits"])
        print(json.dumps({
            "workload": cell.name, "seed": seed, "correct": ok,
            "program": {k: c["value"] for k, c in checks.items()},
            "control": control, "control_correct": ctl_ok,
            "metrics": {k: m["value"] for k, m in metrics.items()},
            "sample_packets": int(len(served.sample.index)),
            "sample_rows": served.sample.n_groups,
            "deepest": served.sample.deepest,
            "served": int(len(served.verdicts)),
            "run_s": t1 - t0, "control_s": time.perf_counter() - t1}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
