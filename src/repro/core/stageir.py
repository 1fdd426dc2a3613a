"""Stage-based pipeline IR (paper §3.3, refactored).

Every backend lowers a ``TrainedModel`` into a typed list of ``Stage`` ops
instead of an opaque per-backend closure.  The vocabulary mirrors what the
paper's templates instantiate on hardware:

  ``FeatureSelect``     pick the feature subset a model consumes
  ``Dense``             one affine layer (+ optional ReLU) — a Taurus
                        map x reduce-tree dot-product template
  ``FusedMLP``          a whole ReLU-MLP executed as ONE Pallas kernel
                        launch (the Taurus MapReduce grid on TPU)
  ``CentroidDistance``  squared distances to K centroids (KMeans table)
  ``Quantize``          per-feature range tables: value -> bucket id
  ``LUTGather``         per-feature MATs: bucket -> per-class partials,
                        summed across features
  ``TreeTraverse``      level-synchronous decision-tree walks with a
                        majority vote over the trees (one MAT per level
                        on a switch; one tree is the T = 1 forest)
  ``Reduce``            argmax / argmin over class scores
  ``LabelMap``          cluster/leaf id -> class id

Stateful vocabulary (per-flow registers, docs/pipeline_ir.md
#flow-state-contract):

  ``FlowKey``           mix packet header columns into an int32 flow key
  ``RegisterUpdate``    per-flow register file update (counters / EWMAs /
                        windowed histograms) — hash, gather, update,
                        scatter; the Pallas backend fuses it into ONE
                        kernel launch (kernels/flow_update)
  ``WindowStats``       registers -> model-ready windowed statistics
                        (histograms normalized by the packet count)
  ``Mitigate``          verdicts -> actions: per-flow drop/rate-limit
                        action table fed by the classifier's verdicts
                        (must be the LAST stage; ``split_mitigation``)

Stateful stages carry ``stateful = True`` and cannot be compiled
statelessly — ``compile_stages`` rejects them; the serving path is
``repro.flowstate.StatefulPipeline``, which threads a ``FlowState``
through fixed-shape batches.

Two layers of the stack consume the same IR:

  * execution — ``compile_stages`` folds the stage list into one jitted
    JAX program (``apply_stages`` is the traceable form chaining uses to
    inline entire DAGs into a single XLA program); with
    ``backend="pallas"`` kernel-eligible pipelines lower onto ONE fused
    Pallas kernel launch instead (core.pallas_backend);
  * accounting — ``lower_topology`` produces shape-only ``StageSpec``s from
    which the platform resource models (core.feasibility) read layer
    shapes, parameter counts and table counts instead of re-deriving them
    per backend.

A peephole pass (``fuse_pipeline_stages``) rewrites FusedMLP -> Reduce into
``FusedClassify``, which runs the argmax inside the Pallas kernel so class
ids, not logits, cross the kernel boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

# bucket count of the MAT range tables — single source of truth for both
# the executable lowering (codegen._quantize_tables) and the shape-only
# accounting specs below
MAT_BINS = 512

# =========================================================== concrete stages


class Stage:
    """One typed pipeline op: apply() is traceable jnp, meta() is the
    resource metadata feasibility accounting reads."""

    kind: str = "stage"

    def apply(self, h: jax.Array) -> jax.Array:
        raise NotImplementedError

    def meta(self) -> dict:
        return {}

    def __repr__(self):
        m = self.meta()
        inner = ", ".join(f"{k}={v}" for k, v in m.items())
        return f"{type(self).__name__}({inner})"


@dataclasses.dataclass(repr=False)
class FeatureSelect(Stage):
    idx: np.ndarray                      # feature indices to keep

    kind = "feature_select"

    def apply(self, h):
        return h[:, jnp.asarray(np.asarray(self.idx, np.int32))]

    def meta(self):
        return {"n_out": len(self.idx)}


@dataclasses.dataclass(repr=False)
class Dense(Stage):
    w: np.ndarray                        # [n_in, n_out]
    b: np.ndarray                        # [n_out]
    act: str | None = None               # None | "relu"

    kind = "dense"

    def apply(self, h):
        out = jnp.dot(h, jnp.asarray(self.w, jnp.float32),
                      precision=jax.lax.Precision.HIGHEST) + jnp.asarray(
            self.b, jnp.float32
        )
        if self.act == "relu":
            out = jax.nn.relu(out)
        return out

    def meta(self):
        n_in, n_out = self.w.shape
        return {"n_in": n_in, "n_out": n_out,
                "params": int(self.w.size + self.b.size),
                "macs": int(self.w.size)}


@dataclasses.dataclass(repr=False)
class FusedMLP(Stage):
    """Whole ReLU-MLP -> logits in one fused Pallas kernel launch."""

    weights: list[np.ndarray]
    biases: list[np.ndarray]

    kind = "fused_mlp"

    def apply(self, h):
        from repro.kernels.fused_mlp import envelope_reason, fused_mlp
        from repro.kernels.fused_mlp.ref import mlp_ref

        ws = [jnp.asarray(w) for w in self.weights]
        bs = [jnp.asarray(b) for b in self.biases]
        # the interpreter's walk: beyond the kernel's lane tile the
        # stage is plain jnp (the Pallas lowering declines such models)
        if envelope_reason(h.shape[1], ws):
            return mlp_ref(h, ws, bs)
        return fused_mlp(h, ws, bs)

    def meta(self):
        return {
            "widths": [int(self.weights[0].shape[0])]
            + [int(w.shape[1]) for w in self.weights],
            "params": int(sum(w.size + b.size
                              for w, b in zip(self.weights, self.biases))),
            "macs": int(sum(w.size for w in self.weights)),
            "layers": len(self.weights),
        }


@dataclasses.dataclass(repr=False)
class FusedClassify(Stage):
    """FusedMLP + argmax folded into the kernel: class ids out, no logits
    round-trip through HBM.  Produced by ``fuse_pipeline_stages``."""

    weights: list[np.ndarray]
    biases: list[np.ndarray]

    kind = "fused_classify"

    def apply(self, h):
        from repro.kernels.fused_mlp import envelope_reason, fused_mlp_classify
        from repro.kernels.fused_mlp.ref import mlp_ref

        ws = [jnp.asarray(w) for w in self.weights]
        bs = [jnp.asarray(b) for b in self.biases]
        if envelope_reason(h.shape[1], ws):
            return jnp.argmax(mlp_ref(h, ws, bs), -1).astype(jnp.int32)
        return fused_mlp_classify(h, ws, bs)

    def meta(self):
        return FusedMLP(self.weights, self.biases).meta()


@dataclasses.dataclass(repr=False)
class CentroidDistance(Stage):
    centroids: np.ndarray                # [K, F']

    kind = "centroid_distance"

    def apply(self, h):
        cent = jnp.asarray(self.centroids, jnp.float32)
        return jnp.sum((h[:, None, :] - cent[None]) ** 2, -1)

    def meta(self):
        k, f = self.centroids.shape
        return {"n_in": f, "n_out": k, "params": int(self.centroids.size),
                "macs": int(self.centroids.size)}


@dataclasses.dataclass(repr=False)
class Quantize(Stage):
    edges: np.ndarray                    # [F, BINS-1] range-table edges

    kind = "quantize"

    def apply(self, h):
        edges = jnp.asarray(self.edges, jnp.float32)
        return jax.vmap(
            lambda col, e: jnp.searchsorted(e, col), in_axes=(1, 0),
            out_axes=1,
        )(h, edges)

    def meta(self):
        f, bins = self.edges.shape
        return {"n_features": f, "bins": bins + 1}


@dataclasses.dataclass(repr=False)
class LUTGather(Stage):
    tables: np.ndarray                   # [F, BINS, C] per-feature partials

    kind = "lut_gather"

    def apply(self, bins):
        tables = jnp.asarray(self.tables, jnp.float32)
        partial = jax.vmap(
            lambda b, t: t[b], in_axes=(1, 0), out_axes=1
        )(bins, tables)                  # [N, F, C]
        return partial.sum(1)

    def meta(self):
        f, bins, c = self.tables.shape
        return {"n_features": f, "bins": bins, "n_out": c,
                "params": int(self.tables.size)}


@dataclasses.dataclass(repr=False)
class TreeTraverse(Stage):
    """A forest of level-synchronous CART walks with a hard majority vote.

    Each of the T trees walks ``depth + 1`` rounds of gather/compare
    (``x[feat] <= thr`` goes left, as scikit-learn splits) to its leaf
    class; the verdict is the class with the most votes over the trees,
    ties to the lowest class id (as ``Reduce("argmax")`` breaks them).
    One tree is the T = 1 case: its vote is its leaf class.  Node arrays
    carry a leading tree axis, [T, N]; a tree's root is node 0."""

    feat: np.ndarray                     # [T, N] split feature (0 at leaf)
    thr: np.ndarray                      # [T, N] f32 threshold
    left: np.ndarray                     # [T, N] child ids (self at leaf)
    right: np.ndarray
    leaf_class: np.ndarray               # [T, N] class at leaf (0 inner)
    is_leaf: np.ndarray                  # [T, N] bool
    depth: int

    kind = "tree_traverse"

    def __post_init__(self):
        for name, dt in (("feat", np.int32), ("thr", np.float32),
                         ("left", np.int32), ("right", np.int32),
                         ("leaf_class", np.int32), ("is_leaf", bool)):
            setattr(self, name, np.atleast_2d(np.asarray(getattr(self, name),
                                                         dt)))

    @classmethod
    def from_nodes(cls, nodes: list[dict], depth: int) -> "TreeTraverse":
        """One CART tree (``mlalgos.train_tree``'s flat nodes)."""
        return cls.from_forest([nodes], depth)

    @classmethod
    def from_forest(cls, trees: list[list[dict]], depth: int
                    ) -> "TreeTraverse":
        """Trees of flat nodes (``{"feat", "thr", "left", "right"}`` or
        ``{"leaf"}``, root first); shorter trees are padded with
        unreachable leaves."""
        n = max(len(t) for t in trees)
        shape = (len(trees), n)
        feat = np.zeros(shape, np.int32)
        thr = np.zeros(shape, np.float32)
        left = np.broadcast_to(np.arange(n, dtype=np.int32), shape).copy()
        right = left.copy()
        leaf_class = np.zeros(shape, np.int32)
        is_leaf = np.ones(shape, bool)
        for t, nodes in enumerate(trees):
            for i, nd in enumerate(nodes):
                if "leaf" in nd:
                    leaf_class[t, i] = nd["leaf"]
                    continue
                is_leaf[t, i] = False
                feat[t, i] = nd["feat"]
                thr[t, i] = np.float32(nd["thr"])
                left[t, i] = nd["left"]
                right[t, i] = nd["right"]
        return cls(feat, thr, left, right, leaf_class, is_leaf, depth)

    @property
    def n_trees(self) -> int:
        return int(self.feat.shape[0])

    @property
    def n_classes(self) -> int:
        """Vote lanes: a class no leaf holds gets no vote."""
        return int(self.leaf_class.max(initial=0)) + 1

    def heap(self) -> tuple:
        """The forest as complete trees in heap layout -> (feat [T, 2^D],
        thr [T, 2^D], leaf [T, 2^D], D).  Inner node (l, i) sits at column
        ``2^l + i`` (column 0 unused) with children (l+1, 2i) and
        (l+1, 2i+1); leaf i of level D at column i.  An early leaf is
        copied down to depth D behind ``+inf`` thresholds (always left),
        so every walk takes D compare-and-step levels.  D is the deepest
        tree's depth, at most the ``depth + 1`` rounds ``apply`` walks; a
        node still inner after them gives its own ``leaf_class``, as in
        ``apply``."""
        T = self.n_trees
        rows = np.arange(T)[:, None]
        node = np.zeros((T, 1), np.int64)
        levels = []
        while len(levels) <= self.depth and not self.is_leaf[rows, node].all():
            leaf = self.is_leaf[rows, node]
            levels.append((np.where(leaf, 0, self.feat[rows, node]),
                           np.where(leaf, np.inf, self.thr[rows, node])))
            lo = np.where(leaf, node, self.left[rows, node])
            hi = np.where(leaf, node, self.right[rows, node])
            node = np.stack([lo, hi], 2).reshape(T, -1)
        D = len(levels)
        feat = np.zeros((T, 1 << D), np.int32)
        thr = np.zeros((T, 1 << D), np.float32)
        for l, (f, t) in enumerate(levels):
            feat[:, 1 << l:2 << l] = f
            thr[:, 1 << l:2 << l] = t
        return feat, thr, self.leaf_class[rows, node].astype(np.int32), D

    def apply(self, h):
        tree = jnp.arange(self.n_trees)[None, :]
        feat = jnp.asarray(self.feat)
        thr = jnp.asarray(self.thr)
        left = jnp.asarray(self.left)
        right = jnp.asarray(self.right)
        is_leaf = jnp.asarray(self.is_leaf)
        nid = jnp.zeros((h.shape[0], self.n_trees), jnp.int32)
        for _ in range(self.depth + 1):
            x_f = jnp.take_along_axis(h, feat[tree, nid], axis=1)
            child = jnp.where(x_f <= thr[tree, nid], left[tree, nid],
                              right[tree, nid])
            nid = jnp.where(is_leaf[tree, nid], nid, child)
        cls = jnp.asarray(self.leaf_class)[tree, nid]          # [B, T]
        votes = jnp.sum(cls[:, :, None] == jnp.arange(self.n_classes),
                        axis=1)
        return jnp.argmax(votes, -1).astype(jnp.int32)

    def meta(self):
        return {"n_trees": self.n_trees, "n_nodes": int(self.feat.size),
                "depth": self.depth, "params": int(self.feat.size)}


@dataclasses.dataclass(repr=False)
class Reduce(Stage):
    op: str                              # argmax | argmin

    kind = "reduce"

    def apply(self, scores):
        fn = jnp.argmax if self.op == "argmax" else jnp.argmin
        return fn(scores, -1)

    def meta(self):
        return {"op": self.op}


@dataclasses.dataclass(repr=False)
class LabelMap(Stage):
    table: np.ndarray                    # [K] id -> class

    kind = "label_map"

    def apply(self, ids):
        return jnp.asarray(np.asarray(self.table, np.int32))[ids]

    def meta(self):
        return {"n_in": len(self.table)}


# ======================================================== stateful vocabulary
#
# Per-flow register stages (docs/pipeline_ir.md#flow-state-contract).  The
# register-file semantics (layout, eviction, ordering) live in
# repro.flowstate.registers; these stages are the IR wrapping: FlowKey
# derives the key, RegisterUpdate derives the update vectors and owns the
# table spec, WindowStats is the stateless readout the classifier consumes.


@dataclasses.dataclass(repr=False)
class FlowKey(Stage):
    """Mix packet header columns into a non-negative int32 flow key.

    Columns are rounded to int and FNV-folded, so any integral-valued
    header fields (ids, ports, bucketed addresses) compose into one key.
    The sign bit is cleared: the register file reserves -1 for empty."""

    key_cols: tuple                      # packet columns hashed into the key
    n_slots: int                         # table size the key will index

    kind = "flow_key"
    stateful = True

    def apply(self, h):
        raise TypeError(
            "FlowKey is stateful; serve it through "
            "repro.flowstate.StatefulPipeline, not compile_stages"
        )

    def apply_keys(self, h) -> jax.Array:
        """[B, F] packet rows -> [B] int32 flow keys (traceable)."""
        key = jnp.zeros(h.shape[0], jnp.uint32)
        for c in self.key_cols:
            v = jnp.round(h[:, c]).astype(jnp.int32).astype(jnp.uint32)
            key = key * jnp.uint32(16777619) ^ v     # FNV-1a style fold
        return (key & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

    def apply_keys_np(self, h: np.ndarray) -> np.ndarray:
        """Numpy twin of ``apply_keys`` — same fold, same rounding — for
        host-side routing (the sharded engine partitions packets across
        per-device register tables BEFORE any device transfer).  Pinned
        equal to the traceable form in tests/test_sharded_engine.py."""
        h = np.asarray(h)
        key = np.zeros(h.shape[0], np.uint32)
        with np.errstate(over="ignore"):
            for c in self.key_cols:
                v = np.round(h[:, c]).astype(np.int32).astype(np.uint32)
                key = key * np.uint32(16777619) ^ v
        return (key & np.uint32(0x7FFFFFFF)).astype(np.int32)

    def meta(self):
        return {"key_cols": tuple(self.key_cols), "n_slots": self.n_slots}


@dataclasses.dataclass(repr=False)
class RegisterUpdate(Stage):
    """Per-flow register update: the stateful heart of the pipeline.

    Per packet: counter 0 += 1 (packet count — the WindowStats
    normalizer); counter 1+j += packet column ``counter_cols[j]``; EWMA j
    blends packet column ``ewma_cols[j]``; histogram j increments the
    bucket ``searchsorted(hist_edges[j], col)`` of its section.  The
    derivation (``prepare``) is stateless vectorized jnp; the stateful
    scatter/gather itself runs in kernels/flow_update (Pallas) or its jnp
    scan reference — bit-identical either way."""

    spec: "object"                       # flowstate.registers.FlowStateSpec
    counter_cols: tuple = ()             # value-accumulating counters 1..
    ewma_cols: tuple = ()
    hist_cols: tuple = ()
    hist_edges: tuple = ()               # np array of edges per histogram

    kind = "register_update"
    stateful = True

    def __post_init__(self):
        s = self.spec
        if s.n_counters != 1 + len(self.counter_cols):
            raise ValueError(
                f"spec.n_counters={s.n_counters} != 1 (pkt count) + "
                f"{len(self.counter_cols)} counter_cols"
            )
        if s.n_ewma != len(self.ewma_cols):
            raise ValueError("spec.n_ewma != len(ewma_cols)")
        if len(self.hist_cols) != len(self.hist_edges):
            raise ValueError("hist_cols and hist_edges must pair up")
        sizes = tuple(len(np.asarray(e)) + 1 for e in self.hist_edges)
        if tuple(s.hist_sizes) != sizes:
            raise ValueError(
                f"spec.hist_sizes={tuple(s.hist_sizes)} != bins implied by "
                f"hist_edges {sizes}"
            )

    def apply(self, h):
        raise TypeError(
            "RegisterUpdate is stateful; serve it through "
            "repro.flowstate.StatefulPipeline, not compile_stages"
        )

    def prepare(self, h) -> tuple[jax.Array, jax.Array]:
        """[B, F] packet rows -> (upd [B, C+E] f32, bins [B, H] int32
        absolute register columns) — the update vectors the register
        kernel consumes.  Stateless, vectorized, traceable."""
        B = h.shape[0]
        cols = [jnp.ones((B, 1), jnp.float32)]       # counter 0: pkt count
        for c in self.counter_cols:
            cols.append(h[:, c:c + 1])
        for c in self.ewma_cols:
            cols.append(h[:, c:c + 1])
        upd = jnp.concatenate(cols, 1).astype(jnp.float32)
        if not self.hist_cols:
            return upd, jnp.full((B, 1), -1, jnp.int32)
        offs = self.spec.hist_offsets
        bins = [
            (jnp.searchsorted(jnp.asarray(e, jnp.float32), h[:, c])
             .astype(jnp.int32) + offs[j])[:, None]
            for j, (c, e) in enumerate(zip(self.hist_cols, self.hist_edges))
        ]
        return upd, jnp.concatenate(bins, 1)

    def meta(self):
        s = self.spec
        return {
            "n_slots": s.n_slots,
            "width": s.width,
            # stored key + W register words per slot: the SRAM the
            # feasibility oracle charges (matches flowstate_specs)
            "params": s.n_slots * (s.width + 1),
            "sram_bytes": s.sram_bytes,
        }


@dataclasses.dataclass(repr=False)
class WindowStats(Stage):
    """Registers -> model-ready windowed statistics (STATELESS readout).

    ``mode="all"``: [counters ++ EWMAs ++ histograms / packet count];
    ``mode="hist"``: normalized histograms only.  Dividing by the count
    (counter 0) turns raw bin tallies into the paper's flowmarker form —
    partial per-flow distributions comparable across flow ages."""

    spec: "object"
    mode: str = "all"                    # all | hist

    kind = "window_stats"

    def __post_init__(self):
        if self.mode not in ("all", "hist"):
            raise KeyError(f"WindowStats mode must be all|hist: {self.mode}")

    @property
    def n_out(self) -> int:
        s = self.spec
        hist = sum(s.hist_sizes)
        return hist if self.mode == "hist" else s.width

    def apply(self, feats):
        s = self.spec
        head = s.n_counters + s.n_ewma
        denom = jnp.maximum(feats[:, :1], 1.0)       # counter 0 = pkt count
        hist = feats[:, head:] / denom
        if self.mode == "hist":
            return hist
        return jnp.concatenate([feats[:, :head], hist], 1)

    def meta(self):
        return {"n_in": self.spec.width, "n_out": self.n_out,
                "mode": self.mode}


@dataclasses.dataclass(repr=False)
class Mitigate(Stage):
    """Verdicts -> actions: per-flow drop / rate-limit action table.

    Closes the detection loop (docs/pipeline_ir.md#mitigation-contract):
    the classifier's verdict stream feeds a second register file keyed by
    the SAME flow key as the detection table; a flow that accumulates
    ``spec.threshold`` positive verdicts is marked, and its later packets
    are dropped (verdict replaced by ``flowstate.mitigation.MITIGATED``)
    or rate-limited.  Stateful and order-dependent — it must be the LAST
    stage of a stateful pipeline (``split_mitigation``), served through
    ``repro.flowstate.StatefulPipeline``."""

    spec: "object"                       # flowstate.mitigation.MitigationSpec

    kind = "mitigate"
    stateful = True

    def apply(self, h):
        raise TypeError(
            "Mitigate is stateful; serve it through "
            "repro.flowstate.StatefulPipeline, not compile_stages"
        )

    def meta(self):
        s = self.spec
        return {
            "n_slots": s.n_slots,
            "mode": s.mode,
            "threshold": s.threshold,
            # stored key + [hits, since] per slot: the SRAM the
            # feasibility oracle charges (matches mitigation_specs)
            "params": s.n_slots * (s.width + 1),
            "sram_bytes": s.sram_bytes,
        }


def is_stateful(stage: Stage) -> bool:
    return bool(getattr(stage, "stateful", False))


def split_mitigation(stages: list[Stage]
                     ) -> tuple[list[Stage], Mitigate | None]:
    """Split off the trailing ``Mitigate`` stage -> (rest, mitigate|None).

    A mitigation stage consumes the pipeline's *verdicts*, so it can only
    sit LAST; any other placement (or more than one) raises.  The
    remainder is a plain stateful pipeline for ``split_stateful``."""
    mits = [i for i, s in enumerate(stages) if isinstance(s, Mitigate)]
    if not mits:
        return list(stages), None
    if len(mits) > 1 or mits[0] != len(stages) - 1:
        raise ValueError(
            "Mitigate consumes verdicts and must be the single LAST "
            f"stage; got it at positions {mits} of {len(stages)} stages"
        )
    return list(stages[:-1]), stages[-1]


def split_stateful(stages: list[Stage]
                   ) -> tuple[list[Stage], list[Stage]]:
    """Split a stateful pipeline into (prefix, suffix).

    The contract: a stateful pipeline starts with exactly
    ``[FlowKey, RegisterUpdate]``; everything after is a stateless
    classifier over the emitted feature rows (typically starting with
    ``WindowStats``).  Raises on any other arrangement."""
    if len(stages) < 2 or not isinstance(stages[0], FlowKey) \
            or not isinstance(stages[1], RegisterUpdate):
        raise ValueError(
            "stateful pipelines must start with [FlowKey, RegisterUpdate]; "
            f"got {[s.kind for s in stages[:2]]}"
        )
    suffix = list(stages[2:])
    bad = [s.kind for s in suffix if is_stateful(s)]
    if bad:
        raise ValueError(f"stateful stages {bad} outside the prefix")
    return list(stages[:2]), suffix


def split_stateful_multi(stages: list[Stage]
                         ) -> tuple[list[tuple], list[Stage]]:
    """Parse a (possibly multi-table) stateful pipeline -> (groups, suffix).

    Grammar: one or more ``FlowKey RegisterUpdate [WindowStats]`` groups —
    a ``WindowStats`` directly following a ``RegisterUpdate`` is THAT
    table's readout — then a stateless classifier suffix consuming the
    concatenated per-table readouts in group order.  Each group is a
    ``(flow_key, register_update, window_stats | None)`` tuple.  This is
    the multi-table DAG form: every table keys and updates off the SAME
    packet rows, one classifier consumes all their feature rows.  Raises
    on any other arrangement (same per-table contract as
    ``split_stateful``)."""
    groups: list[tuple] = []
    rest = list(stages)
    while rest and isinstance(rest[0], FlowKey):
        if len(rest) < 2 or not isinstance(rest[1], RegisterUpdate):
            raise ValueError(
                "each FlowKey must be followed by its RegisterUpdate; got "
                f"{[s.kind for s in rest[:2]]}"
            )
        ws = rest[2] if len(rest) > 2 and isinstance(rest[2], WindowStats) \
            else None
        groups.append((rest[0], rest[1], ws))
        rest = rest[3 if ws is not None else 2:]
    if not groups:
        raise ValueError(
            "stateful pipelines must start with [FlowKey, RegisterUpdate]; "
            f"got {[s.kind for s in stages[:2]]}"
        )
    bad = [s.kind for s in rest if is_stateful(s)]
    if bad:
        raise ValueError(f"stateful stages {bad} outside the table groups")
    return groups, rest


# ---------------------------------------------------------------- execution


def apply_stages(stages: list[Stage], x: jax.Array) -> jax.Array:
    """Traceable whole-pipeline application (what chaining inlines)."""
    h = x
    for s in stages:
        h = s.apply(h)
    return h


def fuse_pipeline_stages(stages: list[Stage]) -> list[Stage]:
    """Peephole: FusedMLP -> Reduce(argmax) becomes FusedClassify (argmax
    runs inside the Pallas kernel)."""
    out: list[Stage] = []
    i = 0
    while i < len(stages):
        s = stages[i]
        nxt = stages[i + 1] if i + 1 < len(stages) else None
        if (isinstance(s, FusedMLP) and isinstance(nxt, Reduce)
                and nxt.op == "argmax"):
            out.append(FusedClassify(s.weights, s.biases))
            i += 2
            continue
        out.append(s)
        i += 1
    return out


EXEC_BACKENDS = ("interpret", "pallas")

# Engines a compiled artifact may REPORT serving on (what actually runs,
# after fallback): the requestable engines, the whole-DAG megakernel
# (chaining.compile_dag's "pallas-fused-dag"), the single-launch stateful
# pipeline (flowstate.StatefulPipeline's "pallas-fused-flow"), and
# "mixed" for DAGs / stateful pipelines whose parts landed on different
# engines.
REPORT_BACKENDS = ("interpret", "pallas", "pallas-fused-dag",
                   "pallas-fused-flow", "mixed")


class CompiledStages:
    """A jitted whole-pipeline executable with backend provenance.

    Callable like the function ``compile_stages`` used to return;
    ``backend`` records what actually serves ("pallas" when the pipeline
    lowered onto a fused kernel, "interpret" otherwise — including the
    fallback case where Pallas was requested but the stage sequence is
    outside the kernel envelope), ``requested_backend`` what was asked."""

    def __init__(self, fn: Callable, backend: str, requested: str):
        self.fn = jax.jit(fn)
        self.backend = backend
        self.requested_backend = requested

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.fn(x)

    def __repr__(self):
        return f"CompiledStages(backend={self.backend!r})"


def compile_stages(stages: list[Stage], *, fuse: bool = True,
                   backend: str = "interpret") -> CompiledStages:
    """Compile the whole stage list into one XLA program.

    ``backend`` selects the execution engine:

    * ``"interpret"`` (default) — walk the stage list (each ``Stage.apply``
      traced into a single jitted program);
    * ``"pallas"`` — lower the whole pipeline onto ONE fused Pallas kernel
      launch (``core.pallas_backend``) when the stage sequence is
      kernel-eligible per docs/pipeline_ir.md#pallas-lowering-contract;
      ineligible pipelines (or an unavailable Pallas toolchain) fall back
      to the interpreter.

    The returned ``CompiledStages`` is callable and reports the backend
    that actually serves via ``.backend``."""
    if backend not in EXEC_BACKENDS:
        raise KeyError(f"backend must be one of {EXEC_BACKENDS}")
    state_kinds = [s.kind for s in stages if is_stateful(s)]
    if state_kinds:
        raise ValueError(
            f"stateful stages {state_kinds} cannot be compiled statelessly; "
            "use repro.flowstate.StatefulPipeline"
        )
    run_list = fuse_pipeline_stages(stages) if fuse else list(stages)

    if backend == "pallas":
        from repro.core import pallas_backend

        kernel_fn = pallas_backend.lower_stages_pallas(run_list)
        if kernel_fn is not None:
            return CompiledStages(kernel_fn, "pallas", backend)

    return CompiledStages(
        lambda x: apply_stages(run_list, x), "interpret", backend
    )


def stage_summary(stages: list[Stage]) -> dict:
    """Aggregate stage metadata (params/macs/tables) for reports."""
    params = macs = 0
    for s in stages:
        m = s.meta()
        params += m.get("params", 0)
        macs += m.get("macs", 0)
    return {
        "stages": [s.kind for s in stages],
        "params": int(params),
        "macs": int(macs),
    }


# ===================================================== shape-only stage specs
#
# The feasibility oracle runs before anything is trained, so it lowers a
# *topology* into StageSpecs — same vocabulary, shapes only.


@dataclasses.dataclass(frozen=True)
class StageSpec:
    kind: str
    n_in: int = 0
    n_out: int = 0
    params: int = 0
    extra: tuple = ()                    # kind-specific (depth, bins, ...)

    @property
    def is_layer(self) -> bool:
        """Does this spec occupy compute as one dense layer (CU rows)?"""
        return self.kind in ("dense", "centroid_distance")


def lower_topology(algorithm: str, topology: dict, *, form: str = "dense"
                   ) -> list[StageSpec]:
    """Topology dict -> abstract stage list for one backend family.

    ``form="dense"``: Taurus/FPGA/TPU MapReduce lowering.
    ``form="mat"``:   IIsy-style match-action-table lowering.
    """
    if form == "dense":
        return _lower_dense(algorithm, topology)
    if form == "mat":
        return _lower_mat(algorithm, topology)
    raise KeyError(form)


def _dense_widths(topology: dict) -> list[int]:
    return list(topology["widths"])


def _tree_spec(topology: dict) -> StageSpec:
    """A tree (``n_trees`` 1) or forest of ``nodes`` in all: ``params`` is
    ``TreeTraverse.meta()["params"]``, ``extra`` (depth, trees)."""
    return StageSpec("tree_traverse", 0, 0, len(topology["nodes"]),
                     extra=(topology.get("depth", 8),
                            topology.get("n_trees", 1)))


def _lower_dense(algorithm: str, topology: dict) -> list[StageSpec]:
    if algorithm in ("dnn", "logreg"):
        w = _dense_widths(topology)
        specs = [
            StageSpec("dense", w[i], w[i + 1], w[i] * w[i + 1] + w[i + 1])
            for i in range(len(w) - 1)
        ]
        return specs + [StageSpec("reduce")]
    if algorithm == "svm":
        f, c = topology["n_features"], topology["n_classes"]
        return [StageSpec("dense", f, c, f * c + c), StageSpec("reduce")]
    if algorithm == "kmeans":
        f, k = topology["n_features"], topology["k"]
        return [
            StageSpec("centroid_distance", f, k, f * k),
            StageSpec("reduce"),
            StageSpec("label_map", k, k),
        ]
    if algorithm == "tree":
        return [_tree_spec(topology)]
    raise KeyError(f"dense lowering does not map {algorithm}")


def _lower_mat(algorithm: str, topology: dict, bins: int = MAT_BINS
               ) -> list[StageSpec]:
    if algorithm == "svm":
        f, c = topology["n_features"], topology["n_classes"]
        return [
            StageSpec("quantize", f, f, extra=(bins,)),
            StageSpec("lut_gather", f, c, f * bins * c, extra=(bins,)),
            StageSpec("reduce"),
        ]
    if algorithm == "logreg":
        w = _dense_widths(topology)
        f, c = w[0], w[-1]
        return [
            StageSpec("quantize", f, f, extra=(bins,)),
            StageSpec("lut_gather", f, c, f * bins * c, extra=(bins,)),
            StageSpec("reduce"),
        ]
    if algorithm == "kmeans":
        f, k = topology["n_features"], topology["k"]
        return [
            StageSpec("quantize", f, f, extra=(bins,)),
            StageSpec("lut_gather", f, k, f * bins * k, extra=(bins,)),
            StageSpec("reduce"),
            StageSpec("label_map", k, k),
        ]
    if algorithm == "tree":
        return [_tree_spec(topology)]
    if algorithm == "dnn":
        # N2Net-style: each dense layer burns ~12 MATs; keep the dense
        # shapes so the accounting can read layer count
        w = _dense_widths(topology)
        return [
            StageSpec("dense", w[i], w[i + 1], w[i] * w[i + 1] + w[i + 1])
            for i in range(len(w) - 1)
        ] + [StageSpec("reduce")]
    raise KeyError(f"MAT lowering does not map {algorithm}")


def flowstate_specs(spec, *, mode: str = "all") -> list[StageSpec]:
    """Shape-only specs for the stateful prefix + readout — what the
    feasibility oracle charges for the register file
    (``feasibility.flowstate_report``) BEFORE anything is trained.

    ``params`` of the register_update spec is the table's word count
    (stored key + W register words per slot) and must stay equal to
    ``RegisterUpdate.meta()["params"]`` — the conformance suite pins the
    specs-==-stage-meta invariant for the stateful vocabulary too."""
    W = spec.width
    n_out = sum(spec.hist_sizes) if mode == "hist" else W
    return [
        StageSpec("flow_key", n_in=0, n_out=1, extra=(spec.n_slots,)),
        StageSpec("register_update", n_in=W, n_out=W,
                  params=spec.n_slots * (W + 1),
                  extra=(spec.n_slots, W)),
        StageSpec("window_stats", n_in=W, n_out=n_out),
    ]


def mitigation_specs(spec) -> list[StageSpec]:
    """Shape-only spec for the mitigation action table — what
    ``feasibility.mitigation_report`` charges.  ``params`` is the table's
    word count (stored key + [hits, since] per slot) and must stay equal
    to ``Mitigate.meta()["params"]``, like the other stateful specs."""
    W = spec.width
    return [
        StageSpec("mitigate", n_in=1, n_out=1,
                  params=spec.n_slots * (W + 1),
                  extra=(spec.n_slots, W)),
    ]


def spec_layers(specs: list[StageSpec]) -> list[tuple[int, int]]:
    """(n_in, n_out) of every compute layer — what Taurus maps to CU rows."""
    return [(s.n_in, s.n_out) for s in specs if s.is_layer]


def spec_params(specs: list[StageSpec]) -> int:
    return sum(s.params for s in specs)
