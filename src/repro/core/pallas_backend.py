"""Pallas serving backend: stage pipelines -> single fused kernel launches.

The interpreter backend executes a compiled pipeline by walking its stage
list (``stageir.apply_stages``) inside one jitted program.  This module is
the other side of the lowering contract
(docs/pipeline_ir.md#pallas-lowering-contract): it pattern-matches whole
stage sequences and lowers each *kernel-eligible* pipeline onto the
hand-written Pallas kernels, one ``pallas_call`` per pipeline, so a packet
batch makes a single HBM->VMEM round trip and only int32 verdicts cross the
kernel boundary.

Kernel-eligible sequences (an optional leading prelude of
``FeatureSelect`` / ``WindowStats`` stages — cheap elementwise feature
prep — is folded into the kernel's input transform):

  ``FusedClassify``                        -> kernels/fused_mlp (in-kernel
  ``FusedMLP [Reduce(argmax)]``               argmax when a Reduce follows)
  ``Dense(relu)* Dense [Reduce(argmax)]``  -> same kernel: a Dense chain is
                                              packed as MLP layers
  ``Quantize LUTGather Reduce [LabelMap]`` -> kernels/mat_lut (quantize,
                                              LUT gather, arg-reduce and
                                              label rewrite in one launch)

Stateful prefixes (``FlowKey RegisterUpdate``, the flow-state contract)
lower through ``lower_stateful_pallas`` onto kernels/flow_update — the
hash -> gather -> update -> scatter dataflow around ONE kernel launch,
with the register table in HBM and only the batch's rows in VMEM.

Everything else (``CentroidDistance``, ``TreeTraverse``, out-of-envelope
shapes) returns ``None`` and the caller falls back to the interpreter —
``compile_stages``/``compile_dag``/``PacketServeEngine`` record which
backend actually serves.

Lane snapping: in interpret mode (CPU) the fused-MLP kernel pads layers to
the model width rounded to 8 instead of the 128-wide MXU tile — identical
numerics (pad lanes are exact zeros), ~60x fewer FLOPs for the Table-2
sized models, which is what makes this the serving hot path off-TPU too.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from repro.core.stageir import (
    CentroidDistance,
    Dense,
    FeatureSelect,
    FlowKey,
    FusedClassify,
    FusedMLP,
    LabelMap,
    LUTGather,
    Quantize,
    Reduce,
    RegisterUpdate,
    Stage,
    WindowStats,
)

__all__ = [
    "pallas_available",
    "pallas_eligible",
    "lower_stages_pallas",
    "dag_eligible",
    "lower_dag_pallas",
    "stateful_eligible",
    "lower_stateful",
    "lower_mitigation",
    "lower_stateful_pallas",
    "fused_flow_eligible",
    "fused_flow_decline_reason",
    "lower_stateful_fused",
]

# stages foldable into the kernel's input transform: stateless, cheap,
# elementwise-ish feature prep ahead of the fused classifier
_PRELUDE = (FeatureSelect, WindowStats)


def _split_prelude(stages: list[Stage]):
    pre: list[Stage] = []
    body = list(stages)
    while body and isinstance(body[0], _PRELUDE):
        pre.append(body.pop(0))
    return pre, body


def pallas_available() -> bool:
    """Is the Pallas toolchain importable in this process?"""
    try:
        from jax.experimental import pallas  # noqa: F401

        return True
    except Exception:
        return False


def _match_mlp(stages: list[Stage]):
    """-> (weights, biases, classify) for dense/fused-MLP runs, else None."""
    if not stages:
        return None
    classify = False
    body = list(stages)
    if isinstance(body[-1], Reduce):
        if body[-1].op != "argmax":
            return None
        classify = True
        body = body[:-1]
    if len(body) == 1 and isinstance(body[0], (FusedMLP, FusedClassify)):
        if isinstance(body[0], FusedClassify):
            classify = True
        return body[0].weights, body[0].biases, classify
    if body and all(isinstance(s, Dense) for s in body):
        # a Dense chain is an MLP iff activations follow the relu*…linear
        # shape the kernel hard-codes
        if any(s.act != "relu" for s in body[:-1]) or body[-1].act is not None:
            return None
        return ([s.w for s in body], [s.b for s in body], classify)
    return None


def _match_mat(stages: list[Stage]):
    """-> (edges, tables, label_map, use_min) for MAT runs, else None."""
    if len(stages) < 3 or not isinstance(stages[0], Quantize) \
            or not isinstance(stages[1], LUTGather) \
            or not isinstance(stages[2], Reduce):
        return None
    tail = stages[3:]
    if len(tail) > 1 or (tail and not isinstance(tail[0], LabelMap)):
        return None
    tables = np.asarray(stages[1].tables)
    lmap = (np.asarray(tail[0].table, np.int32) if tail
            else np.arange(tables.shape[2], dtype=np.int32))
    return (np.asarray(stages[0].edges), tables, lmap,
            stages[2].op == "argmin")


def _in_envelope_mlp(weights) -> bool:
    from repro.kernels.fused_mlp import envelope_reason

    return envelope_reason(int(weights[0].shape[0]), weights) is None


def _in_envelope_mat(tables, lmap) -> bool:
    from repro.kernels.mat_lut import envelope_reason

    return envelope_reason(tables, lmap) is None


def pallas_eligible(stages: list[Stage]) -> bool:
    """Would ``lower_stages_pallas`` produce a kernel for this pipeline?

    Shape checks only — no parameter packing or device transfers."""
    if not pallas_available():
        return False
    _, body = _split_prelude(stages)
    mlp = _match_mlp(body)
    if mlp is not None:
        return _in_envelope_mlp(mlp[0])
    mat = _match_mat(body)
    if mat is not None:
        return _in_envelope_mat(mat[1], mat[2])
    return False


def lower_stages_pallas(stages: list[Stage]) -> Callable | None:
    """Lower a whole stage list onto one Pallas kernel launch.

    Returns a traceable ``fn(x: [B, F]) -> verdicts/logits`` closing over
    the packed parameters, or ``None`` when the sequence is outside the
    kernel envelope (the caller then falls back to the interpreter)."""
    if not pallas_available():
        return None
    import jax
    import jax.numpy as jnp

    from repro.kernels import fused_mlp as fm_ops
    from repro.kernels import mat_lut as mat_ops
    from repro.kernels.fused_mlp import snap_lane

    pre, body = _split_prelude(stages)

    def pre_fn(x, _pre=tuple(pre)):
        for s in _pre:
            x = s.apply(x)
        return x

    interpret = jax.default_backend() != "tpu"

    mlp = _match_mlp(body)
    if mlp is not None:
        weights, biases, classify = mlp
        if not _in_envelope_mlp(weights):
            return None
        widths = [int(weights[0].shape[0])] + [int(w.shape[1])
                                               for w in weights]
        lane = snap_lane(widths, interpret=interpret)
        ws = [jnp.asarray(w, jnp.float32) for w in weights]
        bs = [jnp.asarray(b, jnp.float32) for b in biases]
        op = fm_ops.fused_mlp_classify if classify else fm_ops.fused_mlp

        def mlp_fn(x, _op=op, _ws=ws, _bs=bs, _lane=lane):
            return _op(pre_fn(x), _ws, _bs, lane=_lane)

        return mlp_fn

    mat = _match_mat(body)
    if mat is not None:
        edges, tables, lmap, use_min = mat
        if not _in_envelope_mat(tables, lmap):
            return None
        edges_j = jnp.asarray(edges, jnp.float32)
        tables_j = jnp.asarray(tables, jnp.float32)
        lmap_j = jnp.asarray(lmap, jnp.int32)

        def mat_fn(x, _e=edges_j, _t=tables_j, _l=lmap_j, _m=use_min):
            return mat_ops.mat_classify(pre_fn(x), _e, _t, _l, use_min=_m)

        return mat_fn

    return None


# ------------------------------------------------------ cross-model DAGs
#
# A Seq/Par DAG whose every leaf is an MLP-shaped classifier lowers onto
# ONE fused Pallas launch (kernels/fused_mlp.fused_dag): all chained
# models' weights resident in VMEM for the launch, Seq gating and Par
# or/and merges applied in-kernel on the int32 verdicts.  Eliminates the
# per-model HBM round trips the per-model-launch path pays between chained
# models; recorded as backend="pallas-fused-dag" by chaining.compile_dag.


def _fold_feature_select(pre: list[Stage], w0: np.ndarray, n_feat: int):
    """Fold a FeatureSelect-only prelude into the first-layer weights.

    ``x[:, idx] @ W0 == x @ S @ W0`` for the 0/1 selection matrix S; with a
    *strictly increasing, duplicate-free* composite index the embedded
    rows keep their original summation order and the interleaved rows are
    exact zeros, so the folded matmul stays bit-identical (the same
    argument that makes lane padding exact).  Returns the [n_feat, h]
    first-layer weights, or ``None`` when the prelude is outside that
    envelope (unsorted/duplicated selection, non-FeatureSelect stages, or
    an index beyond the DAG input width)."""
    if not all(isinstance(s, FeatureSelect) for s in pre):
        return None
    idx = np.asarray(pre[0].idx, np.int64)
    for s in pre[1:]:
        idx = idx[np.asarray(s.idx, np.int64)]
    if idx.size != w0.shape[0] or np.any(np.diff(idx) <= 0):
        return None
    if idx.size and int(idx[-1]) >= n_feat:
        return None
    folded = np.zeros((n_feat, w0.shape[1]), np.float32)
    folded[idx] = np.asarray(w0, np.float32)
    return folded


def _match_dag_leaf(stages: list[Stage]):
    """Post-peephole leaf stage list -> (prelude, weights, biases) for a
    megakernel-eligible classifier, else None.  The leaf must produce
    class-id verdicts (an MLP/Dense chain ending in an in-kernel argmax)."""
    pre, body = _split_prelude(stages)
    if any(not isinstance(s, FeatureSelect) for s in pre):
        return None
    mlp = _match_mlp(body)
    if mlp is None or not mlp[2]:        # gating needs int32 verdicts
        return None
    weights, biases = mlp[0], mlp[1]
    if not _in_envelope_mlp(weights):
        return None
    return pre, list(weights), list(biases)


def _plan_dag(node, result, combine: str, fuse: bool):
    """Walk an Alchemy DAG -> (plan, models) where ``models`` is the
    deduplicated list of (prelude, weights, biases) and ``plan`` the
    nested static structure ``kernels/fused_mlp.eval_dag_plan`` folds.
    Returns None anywhere the DAG leaves the megakernel envelope."""
    from repro.core import stageir
    from repro.core.alchemy import Model, Par, Seq

    models: list = []
    index_of: dict[int, int] = {}        # id(pipeline) -> model slot

    def walk(n):
        if isinstance(n, Model):
            entry = result[n.name]
            pipe = entry.pipeline if hasattr(entry, "pipeline") else entry
            if id(pipe) not in index_of:
                stages = pipe.stages
                if fuse:
                    stages = stageir.fuse_pipeline_stages(stages)
                leaf = _match_dag_leaf(stages)
                if leaf is None:
                    return None
                index_of[id(pipe)] = len(models)
                models.append(leaf)
            return ("model", index_of[id(pipe)])
        if isinstance(n, Seq):
            parts = [walk(c) for c in n.children]
            if any(p is None for p in parts):
                return None
            return ("seq", tuple(parts))
        if isinstance(n, Par):
            if combine not in ("or", "and"):
                return None              # "concat" has no verdict merge
            parts = [walk(c) for c in n.children]
            if any(p is None for p in parts):
                return None
            return (combine, tuple(parts))
        return None

    plan = walk(node)
    if plan is None:
        return None
    return plan, models


def _dag_input_dim(models: list) -> int | None:
    """The DAG input width, read off the no-prelude leaves (every model in
    a DAG consumes the same packet rows).  None when every leaf hides its
    input width behind a FeatureSelect — the fold target is then unknown
    and the DAG falls back to per-model launches."""
    dims = [int(w[0].shape[0]) for pre, w, b in models if not pre]
    if not dims:
        return None
    return max(dims)


def dag_eligible(node, result, *, combine: str = "or",
                 fuse: bool = True) -> bool:
    """Would ``lower_dag_pallas`` fuse this whole DAG into one launch?
    Shape checks only — no parameter packing or device transfers."""
    if not pallas_available():
        return False
    if len(getattr(node, "leaves", lambda: [None])()) < 2:
        return False                     # a bare model is not a DAG
    planned = _plan_dag(node, result, combine, fuse)
    if planned is None:
        return False
    plan, models = planned
    n_feat = _dag_input_dim(models)
    if n_feat is None:
        return False
    import jax

    from repro.kernels import fused_mlp as fm

    interpret = jax.default_backend() != "tpu"
    n_layers, lanes = [], []
    for pre, w, b in models:
        if pre and _fold_feature_select(pre, np.asarray(w[0]), n_feat) is None:
            return False
        if not pre and int(w[0].shape[0]) != n_feat:
            return False
        widths = [n_feat] + [int(x.shape[1]) for x in w]
        if max(widths) > fm.LANE:
            return False
        n_layers.append(len(w))
        lanes.append(fm.snap_lane(widths, interpret=interpret))
    return fm.dag_vmem_bytes(tuple(n_layers), tuple(lanes)) \
        <= fm.DAG_VMEM_BUDGET


def lower_dag_pallas(node, result, *, combine: str = "or",
                     fuse: bool = True):
    """Lower a whole Seq/Par DAG onto ONE fused Pallas kernel launch.

    Returns a traceable ``fn(x: [B, F]) -> verdicts [B] int32`` closing
    over every model's packed weight stacks, or ``None`` when any leaf (or
    the DAG shape itself) is outside the megakernel envelope — the caller
    then falls back to per-model launches.  Bit-exact vs ``run_dag`` by
    the same padding/masking arguments as the single-model kernel."""
    if not pallas_available():
        return None
    if len(getattr(node, "leaves", lambda: [None, None])()) < 2:
        return None
    planned = _plan_dag(node, result, combine, fuse)
    if planned is None:
        return None
    plan, models = planned
    n_feat = _dag_input_dim(models)
    if n_feat is None:
        return None

    import jax
    import jax.numpy as jnp

    from repro.kernels import fused_mlp as fm

    folded: list[tuple[list, list]] = []
    widths_all: list[int] = [n_feat]
    for pre, weights, biases in models:
        w0 = np.asarray(weights[0], np.float32)
        if pre:
            w0 = _fold_feature_select(pre, w0, n_feat)
            if w0 is None:
                return None
        elif w0.shape[0] != n_feat:
            return None                  # inconsistent leaf input widths
        ws = [w0] + [np.asarray(w, np.float32) for w in weights[1:]]
        widths_all += [int(w.shape[1]) for w in ws]
        folded.append((ws, [np.asarray(b, np.float32) for b in biases]))

    interpret = jax.default_backend() != "tpu"
    if max(widths_all) > fm.LANE:
        return None

    # each model keeps its own snapped lane (the per-model path's tile
    # choice), so the fused launch does the same FLOPs as per-model
    # launches and only removes the inter-model HBM round trips
    stacks: list = []
    lanes: list[int] = []
    for ws, bs in folded:
        lane = fm.snap_lane(
            [n_feat] + [int(w.shape[1]) for w in ws], interpret=interpret
        )
        lanes.append(lane)
        w_stack, b_stack = fm.pack_params(
            [jnp.asarray(w) for w in ws], [jnp.asarray(b) for b in bs], lane
        )
        stacks += [w_stack, b_stack]
    stacks = tuple(stacks)
    n_layers = tuple(len(ws) for ws, _ in folded)
    n_classes = tuple(int(ws[-1].shape[1]) for ws, _ in folded)
    if fm.dag_vmem_bytes(n_layers, tuple(lanes)) > fm.DAG_VMEM_BUDGET:
        return None                      # cannot be VMEM-resident: fall back

    def dag_fn(x, _stacks=stacks, _nl=n_layers, _nc=n_classes,
               _lanes=tuple(lanes), _plan=plan, _interp=interpret):
        return fm.fused_dag(
            x, _stacks, n_layers=_nl, n_classes=_nc, lanes=_lanes,
            plan=_plan, interpret=_interp,
        )

    return dag_fn


# ------------------------------------------------------- stateful prefixes


def stateful_eligible(prefix: list[Stage]) -> bool:
    """Would ``lower_stateful_pallas`` fuse this ``[FlowKey,
    RegisterUpdate]`` prefix?  Shape checks only."""
    if not pallas_available():
        return False
    if len(prefix) != 2 or not isinstance(prefix[0], FlowKey) \
            or not isinstance(prefix[1], RegisterUpdate):
        return False
    return _table_envelope_reason(prefix[1].spec) is None


def _table_envelope_reason(spec) -> str | None:
    from repro.kernels import flow_update as fu

    return fu.envelope_reason(spec.n_slots, spec.width,
                              len(spec.hist_sizes))


def lower_stateful(prefix: list[Stage], backend: str
                   ) -> tuple[Callable, str]:
    """Lower a ``[FlowKey, RegisterUpdate]`` prefix for one engine.

    -> (traceable ``fn(keys, regs, x, valid) -> (keys', regs', feats)``,
    the engine that actually serves).  Key derivation and update-vector
    prep are vectorized jnp either way; the hash/gather/update/scatter
    chain is the fused Pallas kernel (kernels/flow_update) when
    ``backend="pallas"`` and the table fits the kernel envelope, else the
    jnp scan reference — bit-identical per the flow-state contract.  This
    is the ONE place the prefix calling convention is wired; every
    stateful consumer goes through it."""
    use_kernel = backend == "pallas" and stateful_eligible(prefix)
    from repro.kernels import flow_update as fu

    fk, ru = prefix
    spec = ru.spec
    update = fu.flow_update if use_kernel else fu.flow_update_ref

    def flow_fn(keys, regs, x, valid, _fk=fk, _ru=ru, _spec=spec,
                _update=update):
        pkt_keys = _fk.apply_keys(x)
        upd, bins = _ru.prepare(x)
        return _update(
            keys, regs, pkt_keys, upd, bins, valid,
            n_counters=_spec.n_counters, n_ewma=_spec.n_ewma,
            alpha=_spec.ewma_alpha,
        )

    return flow_fn, ("pallas" if use_kernel else "interpret")


def lower_mitigation(mit) -> tuple[Callable, str]:
    """Lower a trailing ``Mitigate`` stage for the SPLIT serving path.

    -> (traceable ``fn(mit_keys, mit_regs, pkt_keys, verdicts, valid) ->
    (mit_keys', mit_regs', out_verdicts)``, the engine that actually
    serves).  The fused launch folds the action table in-kernel
    (``lower_stateful_fused`` with ``mitigation=``); this split form is
    the fallback when the rest of the pipeline is outside the fused
    envelope.  Here the action-table scan is the order-dependent shared
    jnp reference (flowstate.mitigation.mitigate_update), so the engine
    is always ``"interpret"`` — reported honestly: ``StatefulPipeline``
    composes it into ``"mixed"`` when the detection half serves on
    Pallas.  Bit-identical to the fused form per the mitigation
    contract."""
    from repro.flowstate.mitigation import mitigate_update

    spec = mit.spec

    def mit_fn(mit_keys, mit_regs, pkt_keys, verdicts, valid, _spec=spec):
        return mitigate_update(mit_keys, mit_regs, pkt_keys, verdicts,
                               valid, spec=_spec)

    return mit_fn, "interpret"


def lower_stateful_pallas(prefix: list[Stage]) -> Callable | None:
    """Kernel-or-None form of ``lower_stateful`` (mirrors
    ``lower_stages_pallas``): the fused flow-update launch, or ``None``
    when the table is outside the kernel envelope."""
    if not stateful_eligible(prefix):
        return None
    return lower_stateful(prefix, "pallas")[0]


# ------------------------------------------------- fully-fused flow path
#
# The whole stateful pipeline — FlowKey -> RegisterUpdate -> feature-emit
# -> classifier [-> Mitigate] — as ONE Pallas launch (kernels/fused_flow):
# the batch's table rows, the classifier parameters AND the action-table
# update co-resident in VMEM, feature rows consumed in-kernel, only the
# post-update rows and int32 verdicts leaving (the tables stay in HBM).
# The fused envelope covers
# MLP, MAT (Quantize -> LUTGather -> Reduce -> [LabelMap]) and
# CentroidDistance suffixes, plus multi-table DAGs (several FlowKey /
# RegisterUpdate groups feeding one classifier).  StatefulPipeline tries
# this form FIRST under backend="pallas" and reports "pallas-fused-flow"
# when it serves; `fused_flow_decline_reason` names WHY a pipeline fell
# back to the split composition (surfaced in ServeStats / the journal).


def _match_centroid(stages: list[Stage]):
    """-> (feature_idx | None, centroids, label_map, use_min) when the
    stage run is ``[FeatureSelect?] CentroidDistance Reduce [LabelMap?]``,
    else None."""
    body = list(stages)
    fidx = None
    if body and isinstance(body[0], FeatureSelect):
        fidx = tuple(int(i) for i in np.asarray(body[0].idx).ravel())
        body = body[1:]
    if len(body) < 2 or not isinstance(body[0], CentroidDistance) \
            or not isinstance(body[1], Reduce):
        return None
    tail = body[2:]
    if len(tail) > 1 or (tail and not isinstance(tail[0], LabelMap)):
        return None
    cent = np.asarray(body[0].centroids, np.float32)
    lmap = (np.asarray(tail[0].table, np.int32) if tail
            else np.arange(cent.shape[0], dtype=np.int32))
    return fidx, cent, lmap, body[1].op == "argmin"


def _as_table_groups(prefix_or_groups):
    """Normalize the prefix argument: a plain ``[FlowKey, RegisterUpdate]``
    prefix (the single-table form) or a ``split_stateful_multi`` group
    list -> list of (flow_key, register_update, window_stats | None)."""
    seq = list(prefix_or_groups)
    if seq and isinstance(seq[0], FlowKey):
        if len(seq) != 2 or not isinstance(seq[1], RegisterUpdate):
            return None
        return [(seq[0], seq[1], None)]
    groups = []
    for g in seq:
        g = tuple(g)
        if len(g) == 2:
            g = (g[0], g[1], None)
        if len(g) != 3 or not isinstance(g[0], FlowKey) \
                or not isinstance(g[1], RegisterUpdate):
            return None
        groups.append(g)
    return groups or None


def _plan_fused(prefix_or_groups, suffix: list[Stage], mitigation=None):
    """Pattern-match the WHOLE fused launch -> (desc, reason) with exactly
    one of the two non-None.  ``desc`` carries everything the lowering
    needs: the folded table groups + readout modes, a tagged suffix
    descriptor, and the mitigation spec.  ``reason`` is the short honest
    decline string surfaced by ``fused_flow_decline_reason``."""
    from repro.kernels.fused_flow import LANE as FF_LANE

    groups = _as_table_groups(prefix_or_groups)
    if groups is None:
        return None, "no [FlowKey, RegisterUpdate] table groups"
    body = list(suffix)
    # single-table back-compat: a leading suffix WindowStats is that
    # table's readout (multi-table groups carry theirs explicitly)
    if len(groups) == 1 and groups[0][2] is None and body \
            and isinstance(body[0], WindowStats):
        groups[0] = (groups[0][0], groups[0][1], body[0])
        body = body[1:]

    modes, n_in = [], 0
    for fk, ru, ws in groups:
        spec = ru.spec
        reason = _table_envelope_reason(spec)
        if reason:
            return None, reason
        if spec.width > FF_LANE:
            return None, "register width exceeds the kernel lane"
        if ws is None:
            modes.append("raw")
            n_in += spec.width
        else:
            s = ws.spec
            if (s.width != spec.width or s.n_counters != spec.n_counters
                    or s.n_ewma != spec.n_ewma):
                return None, "WindowStats readout disagrees with its table"
            modes.append(ws.mode)
            n_in += ws.n_out

    if mitigation is not None:
        from repro.kernels import flow_update as fu

        reason = fu.envelope_reason(mitigation.spec.n_slots, 2, 0)
        if reason:
            return None, "mitigation: " + reason

    mlp = _match_mlp(body)
    if mlp is not None:
        weights, biases, classify = mlp
        if not classify:
            return None, "classifier lacks an in-kernel argmax reduce"
        if int(weights[0].shape[0]) != n_in:
            return None, "classifier input width mismatch"
        widths = [n_in] + [int(w.shape[1]) for w in weights]
        if max(widths) > FF_LANE:
            return None, "classifier width exceeds the kernel lane"
        sfx = ("mlp", list(weights), list(biases))
    else:
        mat = _match_mat(body)
        if mat is not None:
            edges, tables, lmap, use_min = mat
            if int(edges.shape[0]) != n_in:
                return None, "classifier input width mismatch"
            if not _in_envelope_mat(tables, lmap):
                return None, "MAT shape outside the kernel envelope"
            sfx = ("mat", edges, tables, lmap, use_min)
        else:
            cen = _match_centroid(body)
            if cen is None:
                return None, "suffix is not a fused-envelope classifier"
            fidx, cent, lmap, use_min = cen
            if fidx is not None:
                if max(fidx, default=-1) >= n_in \
                        or cent.shape[1] != len(fidx):
                    return None, "classifier input width mismatch"
            elif cent.shape[1] != n_in:
                return None, "classifier input width mismatch"
            if cent.shape[0] > FF_LANE or lmap.shape[0] > FF_LANE \
                    or cent.shape[1] > FF_LANE:
                return None, "centroid shape outside the kernel envelope"
            sfx = ("centroid", fidx, cent, lmap, use_min)

    mit_spec = mitigation.spec if mitigation is not None else None
    return (groups, tuple(modes), sfx, mit_spec), None


def fused_flow_decline_reason(prefix_or_groups, suffix: list[Stage],
                              mitigation=None) -> str | None:
    """Why would ``lower_stateful_fused`` decline this pipeline?

    ``None`` means the single-launch form serves.  Shape checks only —
    no parameter packing or device transfers.  The string is the honest
    fallback reason the serving engines surface (ServeStats backend keys,
    ``backend_fallback`` journal events)."""
    if not pallas_available():
        return "pallas toolchain unavailable"
    _, reason = _plan_fused(prefix_or_groups, suffix, mitigation)
    return reason


def fused_flow_eligible(prefix_or_groups, suffix: list[Stage],
                        mitigation=None) -> bool:
    """Would ``lower_stateful_fused`` produce the single-launch form?
    Shape checks only — no parameter packing or device transfers."""
    return fused_flow_decline_reason(prefix_or_groups, suffix,
                                     mitigation) is None


def _pack_suffix(sfx, tile: int, interpret: bool):
    """Suffix descriptor -> (SuffixPlan, pre-padded device arrays).

    Packing happens ONCE here, at lowering time: lane-snapped MLP stacks
    (``fused_mlp.pack_params``), +inf-padded MAT edges / zero-padded
    tables (the exact ``mat_lut.mat_classify`` convention, so the in-
    kernel replay sees identical operands), zero-padded centroid rows
    (pad lanes contribute exact zeros to the squared distances)."""
    import jax.numpy as jnp

    from repro.kernels.fused_flow import SuffixPlan
    from repro.kernels.fused_mlp import pack_params, snap_lane
    from repro.kernels.mat_lut.ops import _snap

    if sfx[0] == "mlp":
        _, weights, biases = sfx
        widths = [int(weights[0].shape[0])] + [int(w.shape[1])
                                               for w in weights]
        lane = snap_lane(widths, interpret=interpret)
        w_stack, b_stack = pack_params(
            [jnp.asarray(w, jnp.float32) for w in weights],
            [jnp.asarray(b, jnp.float32) for b in biases],
            lane,
        )
        sp = SuffixPlan("mlp", int(weights[-1].shape[1]),
                        n_layers=len(weights), lane=lane)
        return sp, (w_stack, b_stack)
    if sfx[0] == "mat":
        _, edges, tables, lmap, use_min = sfx
        F, bins, C = tables.shape
        K = lmap.shape[0]
        edges_j = jnp.pad(
            jnp.asarray(edges, jnp.float32),
            ((0, _snap(F, 8) - F), (0, _snap(edges.shape[1], tile)
                                    - edges.shape[1])),
            constant_values=jnp.inf,
        )
        tables_j = jnp.pad(
            jnp.asarray(tables, jnp.float32),
            ((0, _snap(F, 8) - F), (0, _snap(bins, tile) - bins),
             (0, _snap(C, tile) - C)),
        )
        lmap_j = jnp.pad(
            jnp.asarray(lmap, jnp.float32), (0, _snap(K, tile) - K)
        )[None, :]
        sp = SuffixPlan("mat", int(C), n_features=int(F), use_min=use_min)
        return sp, (edges_j, tables_j, lmap_j)
    _, fidx, cent, lmap, use_min = sfx
    K, Fp = cent.shape
    nk = lmap.shape[0]
    cent_j = jnp.pad(
        jnp.asarray(cent, jnp.float32),
        ((0, _snap(K, 8) - K), (0, _snap(Fp, tile) - Fp)),
    )
    lmap_j = jnp.pad(
        jnp.asarray(lmap, jnp.float32), (0, _snap(max(K, nk), tile) - nk)
    )[None, :]
    sp = SuffixPlan("centroid", int(K), use_min=use_min,
                    n_centroids=int(K),
                    feature_idx=tuple(fidx) if fidx else ())
    return sp, (cent_j, lmap_j)


def lower_stateful_fused(prefix_or_groups, suffix: list[Stage],
                         mitigation=None) -> Callable | None:
    """Lower the WHOLE stateful pipeline onto one fused Pallas launch.

    ``prefix_or_groups`` is a ``[FlowKey, RegisterUpdate]`` prefix or a
    ``split_stateful_multi`` group list; ``suffix`` must be post-peephole
    (``fuse_pipeline_stages``); ``mitigation`` an optional trailing
    ``Mitigate`` stage folded into the same launch.  Returns a traceable
    ``fn(k0, r0, [k1, r1, ...,] [mit_keys, mit_regs,] x, valid) ->
    (same state arrays updated ..., verdicts)`` closing over the packed
    classifier parameters, or ``None`` when the pipeline is outside the
    fused envelope (``fused_flow_decline_reason`` says why) — the caller
    then composes the split lowerings as before."""
    if not fused_flow_eligible(prefix_or_groups, suffix, mitigation):
        return None
    import jax

    from repro.kernels import fused_flow as ff

    desc, _ = _plan_fused(prefix_or_groups, suffix, mitigation)
    groups, modes, sfx, mit_spec = desc
    interpret = jax.default_backend() != "tpu"
    tile = 8 if interpret else ff.LANE
    table_plans = tuple(
        ff.TablePlan(ru.spec.n_counters, ru.spec.n_ewma,
                     len(ru.spec.hist_sizes), float(ru.spec.ewma_alpha),
                     ru.spec.width, mode)
        for (fk, ru, ws), mode in zip(groups, modes)
    )
    sp, arrays = _pack_suffix(sfx, tile, interpret)
    nt = len(groups)
    stages_fk_ru = tuple((fk, ru) for fk, ru, _ in groups)

    def fused_fn(*args, _groups=stages_fk_ru, _tp=table_plans, _sp=sp,
                 _arrays=arrays, _mspec=mit_spec, _nt=nt,
                 _interp=interpret):
        x, valid = args[-2], args[-1]
        st = args[:-2]
        tbls = []
        for t, (fk, ru) in enumerate(_groups):
            pkt_keys = fk.apply_keys(x)
            upd, bins = ru.prepare(x)
            tbls.append((st[2 * t], st[2 * t + 1], pkt_keys, upd, bins))
        mit_arg = None
        if _mspec is not None:
            mit_arg = (st[2 * _nt], st[2 * _nt + 1], _mspec)
        return ff.fused_flow_serve(
            tbls, valid, _tp, _sp, _arrays, mitigation=mit_arg,
            interpret=_interp,
        )

    return fused_fn
