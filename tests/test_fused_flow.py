"""Fused single-launch stateful path (kernels/fused_flow): parity,
segmentation ordering, fallback honesty, with_backend regression.

The flow-state contract's fused form: under ``backend="pallas"`` the
whole ``FlowKey -> RegisterUpdate -> feature-emit -> classifier`` chain
runs as ONE Pallas launch, bit-identical to the two-dispatch
prefix+suffix composition — verdicts in arrival order and the same final
register table.  These tests pin the guarantee over the collision
patterns the slot-segmentation prelude must survive: one hot flow (deep
sequential drain), all-distinct keys (pure lockstep rounds), all packets
in the SAME slot with different keys (eviction chain), and ragged-tail
valid masks."""

import numpy as np
import pytest

from repro.core import pallas_backend, stageir
from repro.flowstate import FlowStateSpec, StatefulPipeline
from repro.kernels.fused_flow import fused_flow_classify
from repro.kernels.fused_mlp import pack_params, snap_lane

needs_pallas = pytest.mark.skipif(
    not pallas_backend.pallas_available(),
    reason="Pallas toolchain unavailable in this environment",
)


def _spec(n_slots=16):
    return FlowStateSpec(n_slots=n_slots, n_counters=1, n_ewma=1,
                         hist_sizes=(4,), ewma_alpha=0.25)


def _stages(spec, seed=0):
    rng = np.random.default_rng(seed)
    fk = stageir.FlowKey((0,), spec.n_slots)
    ru = stageir.RegisterUpdate(
        spec, ewma_cols=(1,), hist_cols=(1,),
        hist_edges=(np.linspace(0, 1, spec.hist_sizes[0] + 1)[1:-1],),
    )
    ws = stageir.WindowStats(spec, mode="all")
    w1 = rng.normal(size=(ws.n_out, 6)).astype(np.float32)
    w2 = rng.normal(size=(6, 2)).astype(np.float32)
    mlp = stageir.FusedMLP([w1, w2], [np.zeros(6, np.float32),
                                      np.zeros(2, np.float32)])
    return [fk, ru, ws, mlp, stageir.Reduce("argmax")]


def _same_slot_keys(n, n_slots):
    """n DISTINCT keys that all hash to one slot (eviction chain)."""
    from repro.kernels.flow_update import hash_slot

    cand = np.arange(1, 512 * n_slots, dtype=np.int32)
    slots = np.asarray(hash_slot(cand, n_slots))
    hit = cand[slots == slots[0]]
    assert len(hit) >= n, "widen the candidate scan"
    return hit[:n]


def _traffic(rng, pattern, n, n_slots):
    """[n, 2] packets keyed to exercise one segmentation regime."""
    X = np.zeros((n, 2), np.float32)
    if pattern == "one_hot_flow":       # ~90% one flow: deep drain chain
        hot = rng.random(n) < 0.9
        X[:, 0] = np.where(hot, 7, rng.integers(0, 200, n))
    elif pattern == "all_distinct":     # every key unique: rounds only
        X[:, 0] = np.arange(n) + 1
    elif pattern == "same_slot":        # same slot, different keys: the
        X[:, 0] = _same_slot_keys(n, n_slots)    # eviction chain
    else:                               # mixed collision-heavy
        X[:, 0] = rng.integers(0, 9, n)
    X[:, 1] = rng.random(n)
    return X


@needs_pallas
@pytest.mark.parametrize("pattern", ["one_hot_flow", "all_distinct",
                                     "same_slot", "mixed"])
def test_fused_parity_over_collision_patterns(rng, pattern):
    """Fused launch == interpreter, bit for bit, over a multi-chunk
    stream: verdicts in arrival order AND the final register table."""
    spec = _spec()
    stages = _stages(spec)
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow"
    si, sp = pi.init_state(), pp.init_state()
    for chunk in range(4):
        X = _traffic(rng, pattern, 96, spec.n_slots)
        si, vi = pi(si, X)
        sp, vp = pp(sp, X)
        np.testing.assert_array_equal(vi, vp, err_msg=f"{pattern}#{chunk}")
    np.testing.assert_array_equal(np.asarray(si.keys), np.asarray(sp.keys))
    np.testing.assert_array_equal(np.asarray(si.regs), np.asarray(sp.regs))


@needs_pallas
def test_fused_parity_ragged_valid(rng):
    """Padding rows (valid=0) never touch the table and the live
    verdicts keep arrival order through the inverse permutation."""
    spec = _spec()
    stages = _stages(spec)
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    X = _traffic(rng, "one_hot_flow", 64, spec.n_slots)
    valid = np.ones(64, np.int32)
    valid[40:] = 0                       # ragged tail
    valid[rng.integers(0, 40, 5)] = 0    # holes mid-batch
    si, vi = pi(pi.init_state(), X, valid)
    sp, vp = pp(pp.init_state(), X, valid)
    np.testing.assert_array_equal(np.asarray(si.keys), np.asarray(sp.keys))
    np.testing.assert_array_equal(np.asarray(si.regs), np.asarray(sp.regs))
    np.testing.assert_array_equal(vi[valid != 0], vp[valid != 0])


@needs_pallas
def test_fused_op_matches_stage_walk(rng):
    """kernels/fused_flow.fused_flow_classify directly vs the independent
    interpret path (scan-reference update + stage-walk suffix)."""
    from repro.flowstate.registers import init_state, update_flows

    spec = _spec()
    stages = _stages(spec)
    fk, ru = stages[0], stages[1]
    suffix = stages[2:]
    widths = [w.shape[0] for w in stages[3].weights] + [2]
    lane = snap_lane(widths, interpret=True)
    w_stack, b_stack = pack_params(stages[3].weights, stages[3].biases,
                                   lane)
    st = init_state(spec)
    X = _traffic(rng, "same_slot", 80, spec.n_slots)
    pkt_keys = fk.apply_keys(X)
    upd, bins = ru.prepare(X)
    valid = np.ones(80, np.int32)

    keys2, regs2, verd = fused_flow_classify(
        st.keys, st.regs, pkt_keys, upd, bins, valid, w_stack, b_stack,
        n_counters=spec.n_counters, n_ewma=spec.n_ewma,
        alpha=spec.ewma_alpha, mode="all", num_classes=2, lane=lane,
    )
    st_ref, feats_ref = update_flows(st, pkt_keys, upd, bins, valid)
    verd_ref = stageir.apply_stages(suffix, feats_ref)
    np.testing.assert_array_equal(np.asarray(verd), np.asarray(verd_ref))
    np.testing.assert_array_equal(np.asarray(keys2),
                                  np.asarray(st_ref.keys))
    np.testing.assert_array_equal(np.asarray(regs2),
                                  np.asarray(st_ref.regs))


@needs_pallas
def test_with_backend_preserves_fuse_flag():
    """Regression: with_backend must thread ``fuse`` through — an
    unfused pipeline must not silently come back fused."""
    spec = _spec()
    stages = _stages(spec)
    unfused = StatefulPipeline(stages, backend="pallas", fuse=False)
    assert not unfused.fused and unfused.backend == "pallas"
    again = unfused.with_backend("pallas")
    assert not again.fused and again.backend == "pallas"

    fused = StatefulPipeline(stages, backend="pallas")
    assert fused.fused
    assert fused.with_backend("interpret").backend == "interpret"
    assert fused.with_backend("interpret").with_backend("pallas").fused


@needs_pallas
def test_fused_fallback_stays_honest(rng):
    """A suffix outside the fused envelope must NOT report the fused
    backend — it serves bit-identically to the interpreter and surfaces
    the decline reason on ``fallback_reason``."""
    spec = _spec()
    r = np.random.default_rng(1)
    n_in = stages_out(spec)
    wide = stageir.FusedMLP(          # hidden width > the 128 kernel lane
        [np.asarray(r.normal(size=(n_in, 200)), np.float32),
         np.asarray(r.normal(size=(200, 2)), np.float32)],
        [np.zeros(200, np.float32), np.zeros(2, np.float32)])
    stages = _stages(spec)[:3] + [wide, stageir.Reduce("argmax")]
    pp = StatefulPipeline(stages, backend="pallas")
    assert not pp.fused
    assert pp.fallback_reason == "classifier width exceeds the kernel lane"
    assert pp.backend in ("pallas", "mixed")
    pi = StatefulPipeline(stages)
    X = _traffic(rng, "mixed", 48, spec.n_slots)
    _, vi = pi(pi.init_state(), X)
    _, vp = pp(pp.init_state(), X)
    np.testing.assert_array_equal(vi, vp)


@needs_pallas
def test_table_beyond_envelope_declines_with_reason():
    """A flow table past ``MAX_SLOTS`` is declined at lowering with the
    envelope's reason, and feasibility charges the same refusal: it is
    never handed to the kernel compiler, and the split path it falls to
    reports the reference flow update it runs."""
    from repro.core import feasibility
    from repro.data import traffic
    from repro.kernels.flow_update import MAX_SLOTS

    (fk, ru, ws), _ = traffic.flow_feature_stages(n_slots=2 * MAX_SLOTS)
    r = np.random.default_rng(0)
    mlp = stageir.FusedMLP(
        [r.normal(size=(ws.n_out, 8)).astype(np.float32),
         r.normal(size=(8, 2)).astype(np.float32)],
        [np.zeros(8, np.float32), np.zeros(2, np.float32)])
    pp = StatefulPipeline([fk, ru, ws, mlp, stageir.Reduce("argmax")],
                          backend="pallas")
    assert not pp.fused and pp.flow_backend == "interpret"
    assert pp.backend == "mixed"
    assert "MAX_SLOTS" in pp.fallback_reason
    rep = feasibility.flowstate_report(ru.spec, "tpu")
    assert not rep.feasible
    assert any("MAX_SLOTS" in reason for reason in rep.reasons)


def stages_out(spec):
    """WindowStats(mode='all') output width for ``spec``."""
    return stageir.WindowStats(spec, mode="all").n_out


@needs_pallas
def test_fused_step_through_sharded_engine(rng):
    """ShardedPacketServeEngine wraps the fused step (1-ary mesh on a
    one-device host) and matches the interpreter engine's verdicts."""
    from repro.serve import PacketServeEngine, ShardedPacketServeEngine

    spec = _spec(n_slots=32)
    stages = _stages(spec)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow"
    X = _traffic(rng, "mixed", 300, spec.n_slots)
    sh = ShardedPacketServeEngine(pp, feature_dim=2, max_batch=64,
                                  min_shards=1)
    sh.submit(X)
    vs = sh.flush()
    assert sh.stats()["backend"] == "pallas-fused-flow"
    base = PacketServeEngine(StatefulPipeline(stages), feature_dim=2,
                             max_batch=64)
    base.submit(X)
    np.testing.assert_array_equal(vs, base.flush())


# ------------------------------------------- widened fused envelope
#
# MAT / centroid suffixes, the in-kernel mitigation fold and two-table
# DAGs all serve out of the SAME single launch ("pallas-fused-flow") —
# each pinned bit-identical to the interpreter stage walk over the
# inputs most likely to split the paths: values exactly on quantization
# edges, exact centroid-distance ties, and collision-heavy same-slot
# eviction chains through the action table.


def _mat_suffix(spec, seed=0, n_classes=3):
    """Quantize -> LUTGather -> Reduce -> LabelMap over the ws readout,
    with edge rows placed ON values the readout actually produces
    (integer packet counts, exact 0.25-grid fractions)."""
    rng = np.random.default_rng(seed)
    n_in = stages_out(spec)
    edges = np.zeros((n_in, 3), np.float32)
    edges[0] = [1.0, 2.0, 3.0]         # count feature: exact integers
    edges[1:] = [0.25, 0.5, 0.75]      # boundaries every fraction can hit
    tables = rng.random((n_in, 4, n_classes)).astype(np.float32)
    lmap = np.asarray([0, 1, 1], np.int32)[:n_classes]
    return [stageir.Quantize(edges), stageir.LUTGather(tables),
            stageir.Reduce("argmax"), stageir.LabelMap(lmap)]


@needs_pallas
def test_fused_mat_suffix_on_quantization_boundaries(rng):
    """MAT suffix in the fused launch: inputs landing EXACTLY on bin
    edges bucket identically on both paths (`>` on shared f32 values),
    so verdicts and the register table stay bit-identical."""
    spec = _spec()
    stages = _stages(spec)[:3] + _mat_suffix(spec)
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow"
    si, sp = pi.init_state(), pp.init_state()
    for chunk in range(4):
        X = _traffic(rng, "mixed", 96, spec.n_slots)
        X[:, 1] = (rng.integers(0, 5, 96) * 0.25).astype(np.float32)
        si, vi = pi(si, X)
        sp, vp = pp(sp, X)
        np.testing.assert_array_equal(vi, vp, err_msg=f"chunk {chunk}")
    np.testing.assert_array_equal(np.asarray(si.keys), np.asarray(sp.keys))
    np.testing.assert_array_equal(np.asarray(si.regs), np.asarray(sp.regs))


@needs_pallas
def test_fused_centroid_ties_break_to_lowest_index(rng):
    """Centroid suffix with DUPLICATED centroids: every packet nearest
    the pair is an exact distance tie, and the masked argmin must pick
    the lowest index on both paths (label 9 can never win)."""
    spec = _spec()
    cent = np.asarray([[0.5, 0.25], [4.0, 4.0], [0.5, 0.25]], np.float32)
    stages = _stages(spec)[:3] + [
        stageir.FeatureSelect((0, 2)),
        stageir.CentroidDistance(cent),
        stageir.Reduce("argmin"),
        stageir.LabelMap(np.asarray([5, 7, 9], np.int32)),
    ]
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow"
    si, sp = pi.init_state(), pp.init_state()
    for chunk in range(3):
        X = _traffic(rng, "mixed", 96, spec.n_slots)
        si, vi = pi(si, X)
        sp, vp = pp(sp, X)
        np.testing.assert_array_equal(vi, vp, err_msg=f"chunk {chunk}")
        assert set(np.unique(vp)) <= {5, 7}    # index 2 loses every tie
    np.testing.assert_array_equal(np.asarray(si.regs), np.asarray(sp.regs))


@needs_pallas
def test_fused_mitigation_same_slot_eviction_chain(rng):
    """The in-kernel mitigation fold under the worst segmentation: long
    runs of repeated keys that ALL hash to one detection slot — deep
    drain chains in both tables, threshold crossings mid-chain, and
    evictions resetting the action rows.  Verdict stream (MITIGATED
    sentinels included) and both tables stay bit-identical."""
    from repro.flowstate.mitigation import MITIGATED, MitigationSpec

    spec = _spec()
    n_in = stages_out(spec)
    attack = stageir.FusedMLP(        # always verdicts class 1
        [np.zeros((n_in, 2), np.float32)],
        [np.asarray([0.0, 1.0], np.float32)])
    stages = _stages(spec)[:3] + [
        attack, stageir.Reduce("argmax"),
        stageir.Mitigate(MitigationSpec(n_slots=16, threshold=2)),
    ]
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow"
    si, sp = pi.init_state(), pp.init_state()
    keys = _same_slot_keys(8, spec.n_slots)
    saw_drop = False
    for chunk in range(3):
        X = np.zeros((96, 2), np.float32)
        X[:, 0] = np.repeat(keys, 12)          # 96-deep same-slot chain
        X[:, 1] = rng.random(96)
        si, vi = pi(si, X)
        sp, vp = pp(sp, X)
        np.testing.assert_array_equal(vi, vp, err_msg=f"chunk {chunk}")
        saw_drop = saw_drop or bool(np.any(np.asarray(vp) == MITIGATED))
    assert saw_drop, "threshold never tripped: test traffic too gentle"
    for f in ("keys", "regs", "mit_keys", "mit_regs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(si, f)), np.asarray(getattr(sp, f)),
            err_msg=f"{f} diverged")


def _two_table_stages(spec, spec2, seed=0):
    rng = np.random.default_rng(seed)
    fk, ru, ws = _stages(spec)[:3]
    fk2 = stageir.FlowKey((0,), spec2.n_slots)
    ru2 = stageir.RegisterUpdate(spec2, counter_cols=(0,))
    n_in = ws.n_out + spec2.width
    w1 = rng.normal(size=(n_in, 6)).astype(np.float32)
    w2 = rng.normal(size=(6, 2)).astype(np.float32)
    mlp = stageir.FusedMLP([w1, w2], [np.zeros(6, np.float32),
                                      np.zeros(2, np.float32)])
    return [fk, ru, ws, fk2, ru2, mlp, stageir.Reduce("argmax")]


@needs_pallas
def test_fused_two_table_dag_parity(rng):
    """Two FlowKey/RegisterUpdate tables feeding one classifier fuse
    into ONE launch, bit-identical to a hand-walked reference (per-table
    ``update_flows`` + stage application) and to the interpreter."""
    from repro.flowstate.registers import update_flows, init_state

    spec = _spec()
    spec2 = FlowStateSpec(n_slots=32, n_counters=2, n_ewma=0, hist_sizes=())
    stages = _two_table_stages(spec, spec2)
    fk, ru, ws, fk2, ru2 = stages[:5]
    pi = StatefulPipeline(stages)
    pp = StatefulPipeline(stages, backend="pallas")
    assert pp.backend == "pallas-fused-flow" and pp.n_tables == 2
    si, sp = pi.init_state(), pp.init_state()
    r0, r1 = init_state(spec), init_state(spec2)
    for chunk in range(3):
        X = _traffic(rng, "mixed", 96, spec.n_slots)
        si, vi = pi(si, X)
        sp, vp = pp(sp, X)
        # hand-walked reference, table by table
        import jax.numpy as jnp

        r0, f0 = update_flows(r0, fk.apply_keys(X), *ru.prepare(X))
        r1, f1 = update_flows(r1, fk2.apply_keys(X), *ru2.prepare(X))
        feats = jnp.concatenate([ws.apply(f0), f1], axis=1)
        vr = stageir.apply_stages(stages[5:], feats)
        np.testing.assert_array_equal(vp, vi, err_msg=f"chunk {chunk}")
        np.testing.assert_array_equal(vp, np.asarray(vr),
                                      err_msg=f"ref chunk {chunk}")
    for t, ref in enumerate((r0, r1)):
        np.testing.assert_array_equal(np.asarray(sp.keys_list[t]),
                                      np.asarray(ref.keys))
        np.testing.assert_array_equal(np.asarray(sp.regs_list[t]),
                                      np.asarray(ref.regs))
        np.testing.assert_array_equal(np.asarray(sp.keys_list[t]),
                                      np.asarray(si.keys_list[t]))


@needs_pallas
def test_mitigated_fused_pipeline_survives_swap(rng):
    """Satellite regression: a hot swap installing a mitigated pipeline
    over the SAME specs must come back still fused — reporting
    "pallas-fused-flow", carrying both tables bit-identically."""
    from repro.flowstate.mitigation import MitigationSpec
    from repro.serve import PacketServeEngine

    spec = _spec(n_slots=32)
    mit = stageir.Mitigate(MitigationSpec(n_slots=32, threshold=2))
    X1 = _traffic(rng, "mixed", 200, spec.n_slots)
    X2 = _traffic(rng, "mixed", 200, spec.n_slots)

    def run(backend):
        eng = PacketServeEngine(
            StatefulPipeline(_stages(spec) + [mit], backend=backend),
            feature_dim=2, max_batch=64)
        eng.submit(X1)
        v1 = eng.flush()
        eng.swap(StatefulPipeline(_stages(spec, seed=3) + [mit],
                                  backend=backend))
        eng.submit(X2)
        return eng, np.concatenate([v1, eng.flush()])

    eng_p, vp = run("pallas")
    assert eng_p.backend == "pallas-fused-flow"
    assert eng_p.pipeline.fused and eng_p.pipeline.fallback_reason is None
    assert set(eng_p.stats()["backend_batches"]) == {"pallas-fused-flow"}
    eng_i, vi = run("interpret")
    np.testing.assert_array_equal(vp, vi)
    for f in ("keys", "regs", "mit_keys", "mit_regs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(eng_p.state, f)),
            np.asarray(getattr(eng_i.state, f)), err_msg=f)


@needs_pallas
def test_fallback_reason_surfaced_in_stats_and_journal(rng):
    """Satellite: when the fused lowering declines, the decline reason
    reaches both the ``backend_fallback`` journal event and the
    ``backend_batches`` accounting key."""
    from repro.serve import PacketServeEngine

    spec = _spec()
    r = np.random.default_rng(2)
    n_in = stages_out(spec)
    wide = stageir.FusedMLP(
        [np.asarray(r.normal(size=(n_in, 200)), np.float32),
         np.asarray(r.normal(size=(200, 2)), np.float32)],
        [np.zeros(200, np.float32), np.zeros(2, np.float32)])
    stages = _stages(spec)[:3] + [wide, stageir.Reduce("argmax")]
    eng = PacketServeEngine(StatefulPipeline(stages, backend="pallas"),
                            feature_dim=2, max_batch=32)
    eng.submit(_traffic(rng, "mixed", 64, spec.n_slots))
    eng.flush()
    reason = "classifier width exceeds the kernel lane"
    (key,) = eng.stats()["backend_batches"]
    assert key == f"{eng.backend}({reason})"
    evs = eng.telemetry().journal.events("backend_fallback")
    assert evs and evs[0]["reason"] == reason
