"""Pallas kernel: the WHOLE stateful pipeline in ONE launch.

``FlowKey -> RegisterUpdate -> feature-emit -> classifier [-> Mitigate]``
as one dispatch: the post-update feature rows feed the classifier *inside
the same kernel body*, and the mitigation action table updates on the
classifier's verdicts before they leave.  Only the post-update rows and
int32 verdicts cross the kernel boundary.  This is the Taurus per-packet
story (PAPERS.md): stateful features, the ML decision and the enforcement
action as one dataplane pass.

The tables stay in HBM.  The wrapper's XLA prelude gathers the batch's
slot rows and the epilogue scatters each chain's last row back (see
kernels/flow_update), so the launch's VMEM working set depends on the
batch only.  Inside, the update phases are ``flow_update.kernel.walk`` —
vectorized lockstep rounds over chain ranks plus a scalar-indexed drain —
so state and features are bit-identical to the scan reference by the same
per-slot decomposition.  The launch is described by a static ``Plan``:

  * ``Plan.tables`` — one ``TablePlan`` per flow table.  The suffix runs
    in the FIRST table's sorted (segment) order; a multi-table launch
    gathers every other table's rows into that order in-kernel (a
    scalar-indexed row copy per packet) and concatenates the per-table
    readouts into one classifier input.
  * ``Plan.suffix`` — the classifier form.  ``"mlp"`` is the snapped-lane
    matmul chain (same dot shapes as the stateless fused_mlp lowering);
    ``"mat"`` replays ``mat_lut``'s compare-and-count searchsorted +
    one-hot-matmul MATs on the readout rows; ``"centroid"`` computes the
    per-centroid squared distances (zero-padded lanes contribute exact
    zeros) with the masked arg-reduce and LabelMap rewrite in-kernel.
    ``suffix_readout``/``suffix_verdicts`` are plain jnp.
  * ``Plan.mit`` — the folded action table.  Its [hits, since] scan runs
    on the same ``walk`` schedule as the flow tables, over the action
    table's own slot segmentation, with the mitigation reference's
    per-packet arithmetic; the drop / rate-limit decision rewrites the
    verdict of the packet being stepped.  When the action table has the
    SAME slot count as a single flow table, ``hash(key) & (S-1)`` gives
    identical slots, so the launch reuses the flow table's schedule
    (``MitPlan.shared_seg``: no second sort, no verdict permutation).
    Otherwise the verdicts move from the suffix's order into the action
    table's with a one-hot matmul (exact: small integers).  Every
    quantity is an integer-valued f32 (exact below 2**24), so the result
    is bit-identical to ``flowstate.mitigation.mitigate_update``.

Every matmul runs at ``precision=HIGHEST``: one-hot gathers then copy
their f32 operands exactly, and the MLP suffix agrees with the XLA and
stateless-kernel paths that the contract compares it with.

Grid: (1,) — the update phases are sequential dependency chains; every
operand is one VMEM block, schedule scalars and row indices ride in SMEM
(scalar prefetch).  The launch's scoped-VMEM limit is sized from its own
operands.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flow_update.kernel import (
    LANE,
    block,
    compiler_params,
    flow_phase,
    walk,
)

READOUT_MODES = ("all", "hist", "raw")
SUFFIX_KINDS = ("mlp", "mat", "centroid")

# verdict sentinel for a dropped packet (flowstate.mitigation.MITIGATED)
_MITIGATED = -1

_HIGHEST = jax.lax.Precision.HIGHEST


class TablePlan(NamedTuple):
    """Static description of one flow table's update + readout."""

    n_counters: int
    n_ewma: int
    n_hists: int
    alpha: float
    width: int                 # true register width (pre-padding)
    mode: str                  # readout: all | hist | raw


class SuffixPlan(NamedTuple):
    """Static description of the in-kernel classifier."""

    kind: str                  # mlp | mat | centroid
    num_classes: int           # score lanes before any LabelMap rewrite
    n_layers: int = 0          # mlp: layer count
    lane: int = 0              # mlp: snapped lane
    n_features: int = 0        # mat: real (unpadded) feature count
    use_min: bool = False      # mat/centroid: argmin vs argmax
    n_centroids: int = 0       # centroid: real centroid count
    feature_idx: tuple = ()    # centroid: optional static FeatureSelect


class MitPlan(NamedTuple):
    """Static description of the folded mitigation action table."""

    threshold: int
    keep_every: int
    attack_class: int
    drop: bool                 # mode == "drop" (else rate_limit)
    shared_seg: bool = False   # action slots == flow slots: reuse the
                               # flow table's schedule operands


class Plan(NamedTuple):
    """The whole launch, statically: tables, classifier, action table."""

    tables: tuple              # of TablePlan
    suffix: SuffixPlan
    mit: MitPlan | None = None


# operand count per suffix kind (see the layout walked by _serve_kernel)
N_SUFFIX_OPS = {"mlp": 2, "mat": 3, "centroid": 2}
# VMEM operands per flow table: init, add, val, rank, fresh
N_TABLE_OPS = 5


def n_smem_ops(plan: Plan) -> int:
    """Scalar-prefetch (SMEM) operand count: (sched, drain) per table, a
    row permutation into the first table's order per further table, and
    the action table's own (sched, drain) unless it shares table 0's."""
    nt = len(plan.tables)
    n = 2 * nt + (nt - 1)
    if plan.mit is not None and not plan.mit.shared_seg:
        n += 2
    return n


def n_mit_ops(mp: MitPlan) -> int:
    """Mitigation VMEM operand count: the chain heads' (hits, since) and
    the eviction column; without a shared schedule also its own rank
    column and ``from_v``, the verdict-order permutation."""
    return 3 if mp.shared_seg else 5


# ------------------------------------------------------- suffix evaluation
#
# Plain jnp, run on the kernel's VMEM values.


def suffix_readout(feats, tp: TablePlan):
    """Post-update feature rows -> model-ready readout (WindowStats.apply
    folded statically: same elementwise divide, ``mode`` in
    ``READOUT_MODES`` with ``"raw"`` = no WindowStats stage)."""
    if tp.mode not in READOUT_MODES:
        raise KeyError(f"readout mode must be one of {READOUT_MODES}")
    denom = jnp.maximum(feats[:, :1], 1.0)      # counter 0 = pkt count
    head = tp.n_counters + tp.n_ewma
    if tp.mode == "raw":
        return feats[:, :tp.width]
    if tp.mode == "hist":
        return feats[:, head:tp.width] / denom
    return jnp.concatenate(
        [feats[:, :head], feats[:, head:tp.width] / denom], 1
    )


def _label_rewrite(ids, lmap):
    """LabelMap as a one-hot matvec (exact for int values < 2**24) — the
    same gather-as-matmul idiom as ``mat_lut._kernel``."""
    n_pkt = ids.shape[0]
    k_pad = lmap.shape[1]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (n_pkt, k_pad), 1)
    onehot = (k_iota == ids[:, None]).astype(jnp.float32)
    return jnp.dot(
        onehot, lmap[0].astype(jnp.float32)[:, None],
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )[:, 0].astype(jnp.int32)


def _arg_reduce(scores, n_real: int, use_min: bool):
    """Mask lanes >= ``n_real`` to -/+inf, then argmin/argmax (ties to the
    lowest index, matching the interpreter's Reduce)."""
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    if use_min:
        scores = jnp.where(lane_ids < n_real, scores, jnp.inf)
        return jnp.argmin(scores, axis=1).astype(jnp.int32)
    scores = jnp.where(lane_ids < n_real, scores, -jnp.inf)
    return jnp.argmax(scores, axis=1).astype(jnp.int32)


def suffix_verdicts(z, arrays: tuple, sp: SuffixPlan):
    """Readout rows [B, n_in] -> int32 class ids, per ``sp.kind``.

    ``arrays`` are the PRE-PADDED suffix parameters (packed once at
    lowering time — see ``pallas_backend.lower_stateful_fused``):

      * mlp:      (w_stack [L, lane, lane], b_stack [L, lane]) — the
        snapped-lane matmul chain with -inf argmax masking, identical to
        ``fused_mlp._classify_kernel``;
      * mat:      (edges [F8, E_pad] +inf-padded, tables [F8, BINS, C_pad]
        zero-padded, lmap [1, K_pad]) — per-feature compare-and-count
        searchsorted + one-hot-matmul LUT gathers, identical to
        ``mat_lut._kernel``;
      * centroid: (cent [K8, F_pad] zero-padded, lmap [1, K_pad]) —
        per-centroid squared distances (zero pad lanes add exact zeros),
        +inf-masked arg-reduce, LabelMap rewrite.

    Rows that are all zero (ragged padding) classify to some fixed class
    — the engine slices those verdicts off."""
    z = z.astype(jnp.float32)
    n_pkt = z.shape[0]
    if sp.kind == "mlp":
        w_stack, b_stack = arrays
        h = jnp.pad(z, ((0, 0), (0, sp.lane - z.shape[1])))
        for l in range(sp.n_layers):     # static unroll: whole DNN in-kernel
            w = w_stack[l].astype(jnp.float32)
            h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                        precision=_HIGHEST)
            h = h + b_stack[l][None, :]
            if l < sp.n_layers - 1:
                h = jnp.maximum(h, 0.0)
        lane_ids = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where(lane_ids < sp.num_classes, h, -jnp.inf)
        return jnp.argmax(h, axis=1).astype(jnp.int32)
    if sp.kind == "mat":
        edges, tables, lmap = arrays
        bins_cap = tables.shape[1]
        bin_iota = jax.lax.broadcasted_iota(jnp.int32, (n_pkt, bins_cap), 1)
        scores = jnp.zeros((n_pkt, tables.shape[2]), jnp.float32)
        for f in range(sp.n_features):   # static unroll: one MAT per feature
            col = z[:, f][:, None]
            e = edges[f][None, :]
            # searchsorted(side='left'): bucket = #edges strictly below
            bucket = jnp.sum((col > e).astype(jnp.int32), axis=1)
            onehot = (bin_iota == bucket[:, None]).astype(jnp.float32)
            scores = scores + jnp.dot(
                onehot, tables[f].astype(jnp.float32),
                preferred_element_type=jnp.float32, precision=_HIGHEST,
            )
        ids = _arg_reduce(scores, sp.num_classes, sp.use_min)
        return _label_rewrite(ids, lmap)
    if sp.kind == "centroid":
        cent, lmap = arrays
        if sp.feature_idx:               # folded FeatureSelect: static gather
            z = jnp.concatenate([z[:, i:i + 1] for i in sp.feature_idx], 1)
        zp = jnp.pad(z, ((0, 0), (0, cent.shape[1] - z.shape[1])))
        dists = []
        for k in range(sp.n_centroids):  # static unroll: one centroid each
            d = jnp.sum((zp - cent[k][None, :]) ** 2, axis=1)
            dists.append(d[:, None])
        scores = jnp.concatenate(dists, axis=1)
        k_pad = lmap.shape[1]
        fill = jnp.inf if sp.use_min else -jnp.inf
        scores = jnp.pad(scores, ((0, 0), (0, k_pad - sp.n_centroids)),
                         constant_values=fill)
        ids = _arg_reduce(scores, sp.n_centroids, sp.use_min)
        return _label_rewrite(ids, lmap)
    raise KeyError(f"suffix kind must be one of {SUFFIX_KINDS}")


# ------------------------------------------------------- mitigation phase


def mitigation_phase(sched_ref, drain_ref, rank, init_h, init_s, fresh_ref,
                     hits_ref, since_ref, out_ref, mp: MitPlan):
    """The action-table update, on the flow tables' ``walk`` schedule.

    Operands are in the action table's sorted order: ``rank`` [B, 1] is
    each packet's rank in its action-slot chain, ``init_h``/``init_s``
    the stored [hits, since] of its slot (read by chain heads),
    ``fresh_ref`` whether it evicts.  ``out_ref`` enters holding each
    packet's classifier verdict and leaves holding the final verdict: a
    packet's own row is rewritten only in the step that applies it, so
    the step still reads the classifier's verdict there.  ``hits_ref`` /
    ``since_ref`` leave holding each packet's post-update row.

    The step is ``flowstate.mitigation.mitigate_update``'s per-packet
    arithmetic — the state BEFORE a packet decides its fate."""
    thr = jnp.float32(mp.threshold)

    def step(prev, rows):
        fresh = fresh_ref[rows, :] != 0
        v = out_ref[rows, :]
        h0 = jnp.where(fresh, 0.0, prev[0])
        s0 = jnp.where(fresh, 0.0, prev[1])
        marked = h0 >= thr
        if mp.drop:
            drop = marked
        else:
            # pass every keep_every-th packet of a marked flow through
            # (since is an integer-valued f32 below 2**24: the int
            # remainder equals the f32 mod exactly)
            drop = marked & (jax.lax.rem(s0.astype(jnp.int32),
                                         jnp.int32(mp.keep_every)) != 0)
        out = jnp.where(drop, jnp.int32(_MITIGATED), v)
        h1 = h0 + (v == jnp.int32(mp.attack_class)).astype(jnp.float32)
        s1 = jnp.where(marked, s0 + 1.0, 0.0)
        return [h1, s1], [out]

    hits_ref[...] = jnp.zeros_like(hits_ref)
    since_ref[...] = jnp.zeros_like(since_ref)
    walk(sched_ref, drain_ref, rank, [hits_ref, since_ref],
         [init_h, init_s], step, [out_ref])


def _permute_verdicts(verd, from_v):
    """verd [B, 1] int32 -> verd[from_v] via a one-hot matmul (exact for
    small integers at HIGHEST precision)."""
    n = verd.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
              == from_v).astype(jnp.float32)
    return jnp.dot(onehot, verd.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=_HIGHEST).astype(jnp.int32)


# ------------------------------------------------------------ kernel body


def _serve_kernel(*refs, plan: Plan):
    """One launch: per-table flow phases, suffix classify, optional
    mitigation phase.

    Operand layout (``refs`` = SMEM ++ VMEM inputs ++ outputs ++ scratch):
    SMEM — (sched, drain) per table, then ``perm`` per further table (row
    p of the first table's order is row ``perm[p]`` of this table's),
    then the action table's (sched, drain) unless ``shared_seg``.  VMEM —
    ``N_TABLE_OPS`` per table (``flow_update_padded``'s layout), the
    suffix parameter arrays, then the ``n_mit_ops`` mitigation operands
    (init_h, init_s, fresh[, rank, from_v]).  Outputs — post-update rows
    per table (each in its own sorted order), then (hits, since) when
    mitigated, then the verdict column (first-table order, or the action
    table's order when mitigated).  Scratch — one gather buffer per
    further table."""
    nt = len(plan.tables)
    n_smem = n_smem_ops(plan)
    n_sfx = N_SUFFIX_OPS[plan.suffix.kind]
    n_mit = n_mit_ops(plan.mit) if plan.mit is not None else 0
    n_vin = N_TABLE_OPS * nt + n_sfx + n_mit
    n_out = nt + (2 if plan.mit is not None else 0) + 1
    smem = refs[:n_smem]
    vin = refs[n_smem:n_smem + n_vin]
    outs = refs[n_smem + n_vin:n_smem + n_vin + n_out]
    scratch = refs[n_smem + n_vin + n_out:]

    for t, tp in enumerate(plan.tables):
        flow_phase(smem[2 * t], smem[2 * t + 1],
                   *vin[N_TABLE_OPS * t:N_TABLE_OPS * (t + 1)], outs[t],
                   n_counters=tp.n_counters, n_ewma=tp.n_ewma,
                   alpha=tp.alpha)

    zs = [suffix_readout(outs[0][...], plan.tables[0])]
    for t in range(1, nt):
        # bring table t's rows into the first table's order: one
        # scalar-indexed row copy per packet (bit-exact)
        perm, src, dst = smem[2 * nt + t - 1], outs[t], scratch[t - 1]

        def gather(p, carry, perm=perm, src=src, dst=dst):
            dst[pl.ds(p, 1), :] = src[pl.ds(perm[p], 1), :]
            return carry

        jax.lax.fori_loop(0, dst.shape[0], gather, 0)
        zs.append(suffix_readout(dst[...], plan.tables[t]))
    z = jnp.concatenate(zs, axis=1) if nt > 1 else zs[0]

    cur = N_TABLE_OPS * nt
    s_arrays = tuple(r[...] for r in vin[cur:cur + n_sfx])
    cur += n_sfx
    verd = suffix_verdicts(z, s_arrays, plan.suffix)[:, None]

    vo = outs[-1]
    if plan.mit is None:
        vo[...] = verd
        return
    hits_o, since_o = outs[nt], outs[nt + 1]
    mit_ops = vin[cur:cur + n_mit]
    init_h, init_s, fresh_m = mit_ops[:3]
    if plan.mit.shared_seg:
        # action slots == flow slots: the flow schedule IS the action
        # table's and the suffix's order is already its order
        sched, drain = smem[0], smem[1]
        rank = vin[3][...]
        vo[...] = verd
    else:
        sched, drain = smem[-2], smem[-1]
        rank = mit_ops[3][...]
        vo[...] = _permute_verdicts(verd, mit_ops[4][...])
    mitigation_phase(sched, drain, rank, init_h[...], init_s[...], fresh_m,
                     hits_o, since_o, vo, plan.mit)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def fused_flow_serve_padded(*ops, plan: Plan, interpret: bool = False):
    """Packed operands (layout in ``_serve_kernel``: SMEM operands first)
    -> flat outputs: post-update rows [B_pad, W_pad] per table in its
    sorted order, then (hits, since) [B_pad, 1] f32 when ``plan.mit``,
    then verdicts [B_pad, 1] int32 — first-table sorted order, or the
    action table's sorted order when mitigated."""
    nt = len(plan.tables)
    n_smem = n_smem_ops(plan)
    vin = ops[n_smem:]
    b_pad = vin[0].shape[0]

    out_shape = [jax.ShapeDtypeStruct(vin[N_TABLE_OPS * t].shape,
                                      jnp.float32) for t in range(nt)]
    if plan.mit is not None:
        out_shape += [jax.ShapeDtypeStruct((b_pad, 1), jnp.float32)] * 2
    out_shape.append(jax.ShapeDtypeStruct((b_pad, 1), jnp.int32))
    scratch = [pltpu.VMEM(vin[N_TABLE_OPS * t].shape, jnp.float32)
               for t in range(1, nt)]
    return pl.pallas_call(
        functools.partial(_serve_kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_smem,
            grid=(1,),
            in_specs=[block(a.shape) for a in vin],
            out_specs=[block(s.shape) for s in out_shape],
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params(
            sum(a.size * a.dtype.itemsize for a in vin) * 2
            + sum(s.size * 4 for s in out_shape) * 2 + b_pad * b_pad * 4),
        interpret=interpret,
    )(*ops)
