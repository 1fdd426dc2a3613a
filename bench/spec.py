"""Find everything a run needs by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; a
configuration names its file; a traffic mix is ``bench/traffic/<name>.json``
(which may ``extend`` another mix and override its keys); a per-layer
metric is read by ``bench/metrics/<name>.py``; a classifier suffix kind is
built and referenced by ``bench/suffix/<kind>.py``.  Adding any of them
is adding files and entries: nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _mix_file(name: str, dirs) -> dict:
    for d in dirs:
        path = Path(d) / f"{name}.json"
        if path.exists():
            return load_json(path)
    raise FileNotFoundError(f"no traffic mix {name!r} in {list(map(str, dirs))}")


def traffic_mix(name: str, dirs=(BENCH / "traffic",)) -> dict:
    """The mix ``name`` with every ``extends`` resolved (the file's own
    keys override its base's), looked up in ``dirs`` in order."""
    seen = []
    own = _mix_file(name, dirs)
    while True:
        seen.append(name)
        base_name = own.pop("extends", None)
        if base_name is None:
            return own
        if base_name in seen:
            raise ValueError(f"traffic mix {name!r}: extends loop {seen}")
        base = _mix_file(base_name, dirs)
        base.update(own)
        own, name = base, base_name


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def suffix_kind(kind: str, suffix_dir: Path = BENCH / "suffix"):
    """The module that builds and references a classifier suffix kind."""
    return load_module(suffix_dir / f"{kind}.py", f"bench_suffix_{kind}")


def metric_reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    """``read(ctx)`` of the per-layer metric ``name``."""
    safe = "".join(c if c.isalnum() else "_" for c in name)
    return load_module(metrics_dir / f"{name}.py", f"bench_metric_{safe}").read


@dataclasses.dataclass
class Cell:
    """One workload with its configuration, mix and metrics resolved."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list           # BENCHMARK.json entries reported here
    per_layer: list


def _applies(metric: dict, cell_name: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_here


def cell(name: str, root: Path = ROOT) -> Cell:
    bm = benchmark(root)
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    config = load_json(root / cfg_entry["file"])
    mix = traffic_mix(wl["traffic"], (root / "bench" / "traffic",))
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    here = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, here)]
    return Cell(name, int(wl["chips"]), config, mix, e2e, per_layer)
