"""Configurations, mixes, cells and metrics are found by name: adding them
as files and entries needs no edit of the harness."""

import json
import shutil

import pytest

from bench import spec, work

A = "flow-ddos-mlp.churn-sat"


def test_every_cell_resolves():
    bm = spec.benchmark()
    for wl in bm["workloads"]:
        cell = spec.cell(wl["name"])
        assert cell.config["name"] == wl["config"]
        assert "arrival" in cell.mix and cell.mix["flows_active"] > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_cells_see_their_metrics():
    a = spec.cell(A)
    assert {m["name"] for m in a.end_to_end} == {"pkt_per_s", "setup_s"}
    assert "step_mfu" in {m["name"] for m in a.per_layer}
    assert {m["name"] for m in a.per_layer} == {
        "dispatch_us", "device_idle_share", "step_device_us",
        "prelude_epilogue_us", "fused_flow_kernel_us",
        "fused_flow_roofline", "step_mfu",
        "stage_us", "put_us", "fetch_us", "record_us"}
    d = spec.cell("flow-ddos-mlp-x4.churn-sat")
    assert d.chips == 4 and d.mix["flows_active"] == 4 * a.mix["flows_active"]


def test_added_files_show_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    bm = spec.benchmark()
    # a new configuration, a new mix extending an old one, a new metric
    cfg = spec.load_json(spec.BENCH / "configs" / "flow-ddos-mlp.json")
    cfg["name"] = "flow-ddos-mlp-wide"
    cfg["n_slots"] = 1 << 19
    (root / "bench/configs/flow-ddos-mlp-wide.json").write_text(
        json.dumps(cfg))
    (root / "bench/traffic/churn-small.json").write_text(json.dumps(
        {"extends": "churn-sat", "flows_active": 1024}))
    (root / "bench/metrics/new_counter.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bm["configs"].append({"name": "flow-ddos-mlp-wide", "source": "x",
                          "file": "bench/configs/flow-ddos-mlp-wide.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "wide.small", "config": "flow-ddos-mlp-wide",
                            "traffic": "churn-small", "chips": 1, "why": "x"})
    bm["end_to_end"][0]["workloads"].append("wide.small")
    bm["per_layer"].append({"name": "new_counter", "unit": "1",
                            "better": "lower", "source": "program_counter",
                            "layer": "x", "moves": "pkt_per_s",
                            "workloads": ["wide.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.cell("wide.small", root=root)
    assert cell.config["n_slots"] == 1 << 19
    assert cell.mix["flows_active"] == 1024
    assert cell.mix["arrival"]["mode"] == "backlogged"   # from churn-sat
    assert [m["name"] for m in cell.per_layer] == ["new_counter"]
    read = spec.metric_reader("new_counter", root / "bench" / "metrics")
    assert read(None) == 42.0
    with pytest.raises(KeyError):
        spec.cell("no.such.cell", root=root)


def test_added_suffix_kind_shows_up(tmp_path):
    """A classifier kind is a module in ``bench/suffix/``: its operation
    count reaches the work per packet with no edit of the harness."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    suffix_dir = root / "bench" / "suffix"
    (suffix_dir / "toy.py").write_text(
        "def ops(cfg):\n    return 3 * int(cfg['n_nodes'])\n")
    (suffix_dir / "countless.py").write_text("VERDICT_NUMBER = 'x'\n")
    bm = spec.benchmark()
    cfg = spec.load_json(spec.BENCH / "configs" / "flow-ddos-mlp.json")
    cfg["name"] = "flow-ddos-toy"
    cfg["suffix"] = {"kind": "toy", "n_nodes": 7}
    (root / "bench/configs/flow-ddos-toy.json").write_text(json.dumps(cfg))
    bm["configs"].append({"name": "flow-ddos-toy", "source": "x",
                          "file": "bench/configs/flow-ddos-toy.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "toy.churn", "config": "flow-ddos-toy",
                            "traffic": "churn-sat", "chips": 1, "why": "x"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.get("workloads", []).append("toy.churn")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.cell("toy.churn", root=root)
    assert cell.config["suffix"]["kind"] == "toy"
    assert "fused_flow_roofline" in {m["name"] for m in cell.per_layer}
    # register update 10 and readout 24 (test_work.py), the toy's 3 * 7
    assert work.ops_per_packet(cell.config, suffix_dir) == 10 + 24 + 21
    cell.config["suffix"]["kind"] = "countless"
    with pytest.raises(KeyError, match="countless"):
        work.ops_per_packet(cell.config, suffix_dir)


def test_extends_loop_is_an_error(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps({"extends": "b"}))
    (tmp_path / "b.json").write_text(json.dumps({"extends": "a"}))
    with pytest.raises(ValueError):
        spec.traffic_mix("a", (tmp_path,))
