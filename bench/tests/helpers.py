"""Cells at a size a CPU test holds, and a run of them without a chip."""

from __future__ import annotations

import time
from pathlib import Path

from bench import spec

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell(config: str, traffic: str, chips: int = 1) -> spec.Cell:
    cfg = spec.load_json(DATA / "configs" / f"{config}.json")
    mix = spec.traffic_mix(traffic, (DATA / "traffic", spec.BENCH / "traffic"))
    e2e = [{"name": "pkt_per_s", "unit": "pkt/s"},
           {"name": "setup_s", "unit": "s"}]
    per_layer = [{"name": "dispatch_us", "unit": "us"}]
    return spec.Cell(f"{config}.{traffic}", chips, cfg, mix, e2e, per_layer)


def run_tiny(cell: spec.Cell, seed: int = 5, seconds: float = 0.5) -> dict:
    """A whole run of ``cell`` on the CPU: everything but the look for a
    chip."""
    from bench import run

    devices = run.chips(cell.chips, require_accelerator=False)
    return run.run_cell(cell, seed=seed, seconds=seconds, traced=False,
                        devices=devices, t_start=time.perf_counter())
