"""A ``TreeTraverse`` forest inside the fused flow launch (kernels/fused_flow).

The flow registers, their readout and the forest's majority vote run as
ONE launch: each tree walked level by level on its complete heap form
(``TreeTraverse.heap``), the verdict the class with the most votes, ties
to the lowest class id.  These tests pin, at small sizes in interpret
mode, that the fused verdicts and final table equal the interpreter's
(the stage's own ``apply``) and a plain numpy walk of the stage's nodes:
on complete random forests, on unbalanced trees with early leaves, on
vote ties, on a tree the DSE trains (the T = 1 forest); that a forest
over the VMEM budget declines with its reason and serves off the fused
path; and that the engine reports the fused suffix as counters and its
lowering as a ring span."""

import numpy as np
import pytest

from repro.core import mlalgos, pallas_backend, stageir
from repro.data import traffic
from repro.data.netdata import Dataset
from repro.flowstate import StatefulPipeline

needs_pallas = pytest.mark.skipif(
    not pallas_backend.pallas_available(),
    reason="Pallas toolchain unavailable in this environment",
)

N_SLOTS = 256


def _prefix():
    (fk, ru, ws), _ = traffic.flow_feature_stages(n_slots=N_SLOTS)
    return [fk, ru, ws]


def _packets(n=600, seed=1, scenario="ddos_burst"):
    s = traffic.make_stream(scenario, n_packets=n, seed=seed)
    return s.packets.astype(np.float32), s.labels


def _readouts(X):
    """The readout rows the suffix sees, from the interpreter's prefix."""
    n_in = _prefix()[2].n_out
    pipe = StatefulPipeline(
        _prefix() + [stageir.FeatureSelect(np.arange(n_in))],
        backend="interpret")
    _, z = pipe(pipe.init_state(), X)
    return np.asarray(z, np.float32)


def _complete_forest(rng, T, D, pool, n_classes=2):
    """T complete trees of depth D, heap-numbered (children 2j+1, 2j+2),
    thresholds drawn from ``pool`` (a column per feature)."""
    n_inner = (1 << D) - 1
    n = 2 * n_inner + 1
    nodes = np.arange(n)
    inner = nodes < n_inner
    feat = np.zeros((T, n), np.int32)
    thr = np.zeros((T, n), np.float32)
    leaf = np.zeros((T, n), np.int32)
    feat[:, :n_inner] = rng.integers(0, pool.shape[1], (T, n_inner))
    rows = rng.integers(0, pool.shape[0], (T, n_inner))
    thr[:, :n_inner] = pool[rows, feat[:, :n_inner]]
    leaf[:, n_inner:] = rng.integers(0, n_classes, (T, n - n_inner))
    tile = (T, 1)
    return stageir.TreeTraverse(
        feat, thr, np.tile(np.where(inner, 2 * nodes + 1, nodes), tile),
        np.tile(np.where(inner, 2 * nodes + 2, nodes), tile), leaf,
        np.tile(~inner, tile), D)


def _cart_tree(rng, n_feat, max_depth, pool):
    """One unbalanced tree of flat nodes: each node below ``max_depth``
    splits with probability 0.6, else is a leaf."""
    nodes = []

    def build(depth):
        i = len(nodes)
        nodes.append({})
        if depth == max_depth or rng.random() < 0.4:
            nodes[i] = {"leaf": int(rng.integers(0, 2))}
            return i
        f = int(rng.integers(0, n_feat))
        thr = float(pool[rng.integers(0, len(pool)), f])
        left = build(depth + 1)
        nodes[i] = {"feat": f, "thr": thr, "left": left,
                    "right": build(depth + 1)}
        return i

    build(0)
    return nodes


def _numpy_votes(tree, z):
    """Plain numpy walk of the stage's nodes: per tree follow ``<=`` to a
    leaf, count the leaf classes, argmax (ties to the lowest class)."""
    votes = np.zeros((len(z), tree.n_classes), np.int64)
    for t in range(tree.n_trees):
        for i, row in enumerate(z):
            j = 0
            while not tree.is_leaf[t, j]:
                f = tree.feat[t, j]
                j = tree.left[t, j] if row[f] <= tree.thr[t, j] \
                    else tree.right[t, j]
            votes[i, tree.leaf_class[t, j]] += 1
    return np.argmax(votes, axis=1)


def _serve(stages, X, backend, chunk=200):
    pipe = StatefulPipeline(stages, backend=backend)
    state, out = pipe.init_state(), []
    for i in range(0, len(X), chunk):
        state, v = pipe(state, X[i:i + chunk])
        out.append(np.asarray(v))
    return pipe, state, np.concatenate(out)


def _pool(z, n=15):
    """Per-feature quantiles of real readouts: splits that divide traffic."""
    return np.quantile(z, np.linspace(0.05, 0.95, n), axis=0).astype(
        np.float32)


@needs_pallas
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_forest_equals_stage_and_numpy(seed):
    X, _ = _packets(seed=seed + 3)
    z = _readouts(X)
    tree = _complete_forest(np.random.default_rng(seed), 3, 4, _pool(z))
    fused, st_f, v_f = _serve(_prefix() + [tree], X, "pallas")
    assert fused.backend == "pallas-fused-flow", fused.fallback_reason
    _, st_i, v_i = _serve(_prefix() + [tree], X, "interpret")
    np.testing.assert_array_equal(v_f, v_i)
    np.testing.assert_array_equal(v_f, _numpy_votes(tree, z))
    np.testing.assert_array_equal(np.asarray(st_f.keys),
                                  np.asarray(st_i.keys))
    np.testing.assert_array_equal(np.asarray(st_f.regs),
                                  np.asarray(st_i.regs))
    assert 0 < v_f.mean() < 1          # the splits divide the traffic


@needs_pallas
def test_unbalanced_trees_with_early_leaves():
    X, _ = _packets(seed=7)
    z = _readouts(X)
    rng = np.random.default_rng(4)
    trees = [_cart_tree(rng, z.shape[1], 5, _pool(z)) for _ in range(3)]
    tree = stageir.TreeTraverse.from_forest(trees, depth=5)
    _, _, _, D = tree.heap()
    assert D <= 5 and not tree.is_leaf.all()
    fused, _, v_f = _serve(_prefix() + [tree], X, "pallas")
    assert fused.backend == "pallas-fused-flow", fused.fallback_reason
    _, _, v_i = _serve(_prefix() + [tree], X, "interpret")
    np.testing.assert_array_equal(v_f, v_i)
    np.testing.assert_array_equal(v_f, _numpy_votes(tree, z))


def test_heap_copies_early_leaves_down():
    nodes = [{"feat": 1, "thr": 0.5, "left": 1, "right": 2},
             {"leaf": 1},
             {"feat": 0, "thr": 2.0, "left": 3, "right": 4},
             {"leaf": 0}, {"leaf": 1}]
    feat, thr, leaf, D = stageir.TreeTraverse.from_nodes(nodes, 6).heap()
    assert D == 2
    # column 1 the root, 2-3 level 1: the early leaf always goes left
    assert feat[0, 1:4].tolist() == [1, 0, 0]
    assert thr[0, 1] == np.float32(0.5) and np.isinf(thr[0, 2])
    assert thr[0, 3] == np.float32(2.0)
    assert leaf[0].tolist() == [1, 1, 0, 1]


@needs_pallas
def test_vote_tie_goes_to_class_zero():
    X, _ = _packets(n=200, seed=2)
    # four stumps on the packet count: two always vote 1, two vote 0
    always = [{"feat": 0, "thr": 1e9, "left": 1, "right": 2},
              {"leaf": 1}, {"leaf": 0}]
    never = [{"feat": 0, "thr": 1e9, "left": 1, "right": 2},
             {"leaf": 0}, {"leaf": 1}]
    tree = stageir.TreeTraverse.from_forest([always, never, never, always],
                                            depth=1)
    fused, _, v_f = _serve(_prefix() + [tree], X, "pallas")
    assert fused.backend == "pallas-fused-flow", fused.fallback_reason
    assert (v_f == 0).all()
    _, _, v_i = _serve(_prefix() + [tree], X, "interpret")
    assert (v_i == 0).all()
    # one more vote for class 1 breaks the tie
    tree = stageir.TreeTraverse.from_forest([always] * 3 + [never] * 2,
                                            depth=1)
    assert (_serve(_prefix() + [tree], X, "pallas")[2] == 1).all()


@needs_pallas
def test_single_trained_tree_matches_its_own_predict():
    X, y = _packets(n=800, seed=5)
    z = _readouts(X)
    ds = Dataset("flow-readouts", z, y.astype(np.int32), z, y.astype(np.int32),
                 [f"f{i}" for i in range(z.shape[1])], 2)
    trained = mlalgos.train_tree(ds, max_depth=5, min_leaf=8)
    tree = stageir.TreeTraverse.from_nodes(trained.topology["nodes"],
                                           trained.topology["depth"])
    assert tree.n_trees == 1 and not tree.is_leaf[0, 0]
    fused, _, v_f = _serve(_prefix() + [tree], X, "pallas")
    assert fused.backend == "pallas-fused-flow", fused.fallback_reason
    np.testing.assert_array_equal(v_f, trained.predict(z))


@needs_pallas
def test_forest_over_the_vmem_budget_declines_and_serves_split():
    X, _ = _packets(n=200, seed=6)
    z = _readouts(X)
    # one complete tree of depth 14: its deepest level and leaf select
    # need ~100 MiB of temporaries at the serving batch
    tree = _complete_forest(np.random.default_rng(2), 1, 14, _pool(z))
    pipe, _, v = _serve(_prefix() + [tree], X, "pallas")
    assert pipe.backend != "pallas-fused-flow"
    assert "VMEM" in pipe.fallback_reason
    assert pipe.fused_suffix is None
    _, _, v_i = _serve(_prefix() + [tree], X, "interpret")
    np.testing.assert_array_equal(v, v_i)


@needs_pallas
def test_engine_reports_the_fused_forest():
    from repro.serve.packet_engine import PacketServeEngine

    X, _ = _packets(n=300, seed=8)
    tree = _complete_forest(np.random.default_rng(3), 3, 4,
                            _pool(_readouts(X)))
    eng = PacketServeEngine(StatefulPipeline(_prefix() + [tree],
                                             backend="pallas"),
                            feature_dim=X.shape[1], max_batch=64)
    eng.submit(X)
    assert len(eng.flush()) == len(X)
    counters = {"kind": "forest", "trees": 3, "depth": 4,
                "node_visits_per_packet": 12,
                # feat, thr and leaf rows of 3 trees: 16 columns padded
                # to interpret mode's 32-lane tile, 4 bytes each
                "param_bytes": 3 * 3 * 32 * 4}
    stats = eng.stats()
    assert stats["fused_suffix"] == counters
    assert stats["backend_batches"] == {"pallas-fused-flow": 5}
    lower = [s for s in eng.telemetry().tracer.spans()
             if s.name == "serve.lower"]
    assert len(lower) == 1 and lower[0].args == counters
    assert lower[0].cat == "compile"


def test_stage_meta_and_accounting_specs_agree():
    tree = _complete_forest(np.random.default_rng(0), 5, 3,
                            np.ones((2, 4), np.float32))
    meta = tree.meta()
    assert meta["n_trees"] == 5 and meta["n_nodes"] == 5 * 15
    nodes = [{}] * meta["n_nodes"]
    spec, = stageir.lower_topology(
        "tree", {"nodes": nodes, "depth": 3, "n_trees": 5})
    assert spec.params == meta["params"] and spec.extra == (3, 5)


def test_bf16_parts_sum_back_exactly():
    import jax.numpy as jnp

    from repro.kernels.fused_flow.kernel import _bf16_parts

    rng = np.random.default_rng(9)
    n = np.arange(1, 25, dtype=np.float32)
    z = np.concatenate([
        rng.standard_normal(4096) * 10.0 ** rng.integers(-6, 7, 4096),
        (n[:, None] / n[None, :]).ravel(),        # histogram quotients
        [0.0, 1.0, 1e5 + 1, 2.0 ** -20 + 2.0 ** -43]]).astype(np.float32)
    hi, mid, lo = (np.asarray(p, np.float32)
                   for p in _bf16_parts(jnp.asarray(z)))
    np.testing.assert_array_equal((hi + mid) + lo, z)
