"""The forest suffix kind (``bench/suffix/forest.py``) and its cell: the
reference against the program's stage, the check's allowance for a
division one float32 step off, the operation count, the node-visit
reader, and a whole run at a size a CPU test holds."""

import time
import types

import numpy as np
import pytest

from bench import check, pipelines, run, spec, work
from bench.suffix import forest
from bench.tests.helpers import run_tiny, tiny_cell

CELL = ("tiny-flow-ddos-forest", "tiny-churn")


def _config(name="flow-ddos-forest"):
    return spec.load_json(spec.BENCH / "configs" / f"{name}.json")


def _params():
    """Depth 2: the root splits feature 0 at 0.25; its left child feature
    1 at 0.5 (leaves: class 0, class 1), its right child feature 1 at 0.75
    (leaves: class 1, class 0)."""
    return {"feat": np.asarray([[0, 1, 1]]),
            "thr": np.float32([[0.25, 0.5, 0.75]]),
            "leaf": np.asarray([[0, 1, 1, 0]]), "depth": 2, "n_classes": 2}


def _either_way(z):
    """Every input divided: one float32 step either way."""
    z = np.float32(z)
    return (z, np.nextafter(z, np.float32(-np.inf)),
            np.nextafter(z, np.float32(np.inf)))


def _possible(z, p):
    z, lo, hi = _either_way(z)
    return forest.possible(z, lo, hi, forest.scores(z, p), p, {})


def test_possible_widens_only_one_step_from_a_threshold_on_its_path():
    p = _params()
    step = np.nextafter(np.float32(0.5), np.float32(1))
    ok = _possible([[0.1, 0.5],        # on its path's threshold
                    [0.1, step],       # one step above it
                    [0.1, 0.75],       # on a threshold off its path
                    [0.1, 0.3],        # far from every threshold
                    [0.25, 0.6]], p)   # root either way, same class
    assert ok.sum(1).tolist() == [2, 2, 1, 1, 1]
    assert ok[2, 1] and ok[3, 0] and ok[4, 1]


def test_possible_takes_a_zero_quotient_as_exact():
    p = {"feat": np.asarray([[1]]), "thr": np.float32([[0.0]]),
         "leaf": np.asarray([[0, 1]]), "depth": 1, "n_classes": 2}
    tiny = np.nextafter(np.float32(0), np.float32(1))
    ok = _possible([[3.0, 0.0], [3.0, tiny]], p)
    assert ok.tolist() == [[True, False], [True, True]]


def test_reference_walk_equals_the_program_stage():
    import jax.numpy as jnp

    cfg = _config()["suffix"]
    p = forest.params(cfg)
    rng = np.random.default_rng(11)
    pools = np.asarray(cfg["thresholds"], np.float32).T      # [15, F]
    # readouts on and between the thresholds, as served traffic has them
    z = pools[rng.integers(0, 15, (512, 28)), np.arange(28)]
    z = z * np.where(rng.random(z.shape) < 0.5, 1, 1.01).astype(np.float32)
    stage, = forest.stages(p)
    assert stage.n_trees == 32 and stage.depth == 10
    served = np.asarray(stage.apply(jnp.asarray(z)))
    s = forest.scores(z, p)
    assert s.sum(1).tolist() == [32] * 512
    np.testing.assert_array_equal(served, forest.verdicts(s, p))
    assert 0.2 < served.mean() < 0.8


def test_trees_draw_from_the_stated_quantiles():
    cfg = _config()["suffix"]
    p = forest.params(cfg)
    assert p["feat"].shape == p["thr"].shape == (32, 1023)
    assert p["leaf"].shape == (32, 1024)
    pools = [set(np.float32(q).tolist()) for q in cfg["thresholds"]]
    flat = {len(q) for q in pools}
    assert 1 in flat                       # some features never split
    for f, t in zip(p["feat"].ravel(), p["thr"].ravel()):
        assert len(pools[f]) > 1 and float(t) in pools[f]


def test_ops_per_packet():
    c = _config()
    # per tree and level a compare and a child step, a vote add per
    # tree, a compare per class: 32 * 10 * 2 + 32 + 2
    assert work.suffix_ops(c["suffix"]) == 674
    # + the register update 10 and the readout's 24 divisions
    assert work.ops_per_packet(c) == 708
    assert work.bytes_per_packet(c) == 252


def _ctx(op_ns, op_count, rows=512, batches=10, config=None):
    dev = types.SimpleNamespace(op_ns=op_ns, op_count=op_count)
    red = types.SimpleNamespace(mean=lambda fn: fn(dev))
    win = types.SimpleNamespace(
        stats_open={"batches": 5, "packets": 5 * rows},
        stats_close={"batches": 5 + batches,
                     "packets": (5 + batches) * rows})
    return types.SimpleNamespace(
        reduced=red, served=types.SimpleNamespace(window=win),
        config=config or _config_tiny(), n_devices=1)


def _config_tiny():
    return tiny_cell(*CELL).config


def test_tree_visit_ns_reads_the_programs_counter():
    read = spec.metric_reader("tree_visit_ns")
    # 3 trees of depth 4: 12 node visits a packet; 512 rows a launch
    ctx = _ctx({"fused_flow_serve_padded.7": 61_440 * 4},
               {"fused_flow_serve_padded.7": 4})
    assert read(ctx) == pytest.approx(61_440 / (512 * 12))
    assert read(types.SimpleNamespace(
        reduced=None, served=ctx.served, config=ctx.config)) is None


def test_tree_visit_ns_without_the_counter_reads_none(monkeypatch):
    read = spec.metric_reader("tree_visit_ns")
    ctx = _ctx({"fused_flow_serve_padded.7": 1000},
               {"fused_flow_serve_padded.7": 1})

    class Older:
        """A program whose pipeline reports no fused suffix."""

        def __init__(self, config):
            self.pipeline = object()

    monkeypatch.setattr(pipelines, "Built", Older)
    assert read(ctx) is None


def test_sound_path_is_correct_and_the_control_is_not():
    cell = tiny_cell(*CELL)
    built = pipelines.Built(cell.config)
    assert built.pipeline.backend == "pallas-fused-flow"
    devices = run.chips(1, require_accelerator=False)
    served = run.serve(cell, built, 2**32 + 91, 0.5, devices,
                       time.perf_counter())
    ok, checks = run.judge(served, built)
    assert ok, checks
    assert set(checks) == {"table_words_off", "verdict_mismatch",
                           "unanswered", "non_fused_batches",
                           "window_compiles"}
    ctl = check.control_numbers(served.sample, built)
    bad, _ = check.judge(ctl, cell.config["check"]["limits"])
    assert not bad, ctl
    assert ctl["table_words_off"] > 0


def test_altered_verdicts_are_caught(monkeypatch):
    import jax.numpy as jnp

    from repro.flowstate import StatefulPipeline

    orig = StatefulPipeline.dispatch

    def dispatch(self, state, X, valid=None):
        state, v = orig(self, state, X, valid)
        return state, v.at[::8].set(jnp.where(v[::8] == 1, 0, 1))

    monkeypatch.setattr(StatefulPipeline, "dispatch", dispatch)
    res = run_tiny(tiny_cell(*CELL))
    assert not res["correct"]
    assert res["checks"]["verdict_mismatch"]["value"] > 0
