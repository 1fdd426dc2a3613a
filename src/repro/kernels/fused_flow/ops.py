"""Public op: the whole stateful pipeline as ONE fused kernel launch.

``fused_flow_serve(tables, valid, ...)`` segments the batch by slot once
per flow table (the shared ``flow_update.segment_batch`` prelude) — plus
once more over the action table's own slot space when mitigation is
folded in with a slot count different from the flow table's (same count:
the flow schedule is reused wholesale, ``MitPlan.shared_seg``) — gathers
each packet's slot rows, launches the ``Plan``-driven fused Pallas kernel
(interpret=True on CPU), scatters every chain's last row back into its
table and restores the [B] int32 verdicts to arrival order.  This is the
executable artifact ``core.pallas_backend.lower_stateful_fused`` emits
for a fused-eligible stateful pipeline — the backend string
``"pallas-fused-flow"`` means exactly this launch is serving.
``fused_flow_classify`` keeps the single-table MLP signature as a thin
wrapper.

Suffix parameters arrive PRE-PADDED (lane-snapped MLP stacks, +inf-padded
MAT edges, zero-padded tables/centroids): packing happens once at
lowering time, not per batch.

Bit-identity contract: state, features and verdicts equal the split
composition (flow_update + WindowStats.apply + classifier [+
mitigate_update]) bit for bit — the update phases run the reference's
per-packet arithmetic on the shared ``walk`` schedule (MAT parity
quantization-bounded per the lowering contract).  Tables outside the
kernel envelope raise: the lowering declines them with a reason first,
so there is no silent reference path here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flow_update.ops import (
    _snap,
    chain_fresh,
    col,
    envelope_reason,
    pack_flow_operands,
    padded_rows,
    rows,
    scatter_tails,
    segment_batch,
    smem_vec,
)
from repro.kernels.flow_update.ref import hash_slot
from repro.kernels.fused_flow.kernel import (
    LANE,
    MitPlan,
    Plan,
    SuffixPlan,
    TablePlan,
    fused_flow_serve_padded,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_flow_serve(
    tables,                # seq of (keys [S], regs [S, W], pkt_keys [B],
                           #         upd [B, C+E], bins [B, H])
    valid,                 # [B] int-ish; 0 = padding row, never applied
    table_plans,           # seq of kernel.TablePlan (one per flow table)
    suffix_plan,           # kernel.SuffixPlan
    suffix_arrays,         # tuple of PRE-PADDED suffix parameter arrays
    mitigation=None,       # (mit_keys [Sm], mit_regs [Sm, 2],
                           #  flowstate.mitigation.MitigationSpec)
    interpret: bool | None = None,
):
    """-> flat tuple: per table (keys' [S], regs' [S, W]), then
    (mit_keys', mit_regs') when mitigated, then verdicts [B] int32 in
    ARRIVAL order — one kernel launch.

    Rows with ``valid == 0`` never touch any table and keep meaningless
    verdicts (the engine slices them off).  Bit-identical to the split
    composition; see the flow-state and mitigation contracts in
    docs/pipeline_ir.md.  Raises ``ValueError`` for a table outside the
    kernel envelope."""
    if interpret is None:
        interpret = not _on_tpu()
    tables = [
        (jnp.asarray(k, jnp.int32), jnp.asarray(r, jnp.float32),
         jnp.asarray(pk, jnp.int32), jnp.asarray(u, jnp.float32),
         jnp.asarray(b, jnp.int32))
        for (k, r, pk, u, b) in tables
    ]
    table_plans = tuple(table_plans)
    suffix_arrays = tuple(jnp.asarray(a) for a in suffix_arrays)
    valid = jnp.asarray(valid, jnp.int32)
    nt = len(tables)
    B = int(tables[0][2].shape[0])

    for (k, r, _, _, _), tp in zip(tables, table_plans):
        reason = envelope_reason(int(k.shape[0]), int(r.shape[1]),
                                 tp.n_hists)
        if reason:
            raise ValueError(reason)
    mit_plan = None
    if mitigation is not None:
        mit_keys = jnp.asarray(mitigation[0], jnp.int32)
        mit_regs = jnp.asarray(mitigation[1], jnp.float32)
        mspec = mitigation[2]
        reason = envelope_reason(int(mit_keys.shape[0]), 2, 0)
        if reason:
            raise ValueError("mitigation: " + reason)
        # same slot count as a single flow table -> hash(key) & (S-1)
        # gives identical slots, so the flow schedule is reused wholesale
        shared = (nt == 1
                  and int(mit_keys.shape[0]) == int(tables[0][0].shape[0]))
        mit_plan = MitPlan(mspec.threshold, mspec.keep_every,
                           mspec.attack_class, mspec.mode == "drop",
                           shared_seg=shared)
    if B == 0:
        outs = [a for (k, r, _, _, _) in tables for a in (k, r)]
        if mit_plan is not None:
            outs += [mit_keys, mit_regs]
        return tuple(outs) + (jnp.zeros((0,), jnp.int32),)

    # CPU interpret mode snaps pads to 8-wide tiles; TPU pads the last
    # dim to the full 128 lane.
    tile = 8 if interpret else LANE
    b_pad = padded_rows(B)
    segs = [
        segment_batch(hash_slot(pk, int(k.shape[0])), valid,
                      int(k.shape[0]))
        for (k, _, pk, _, _) in tables
    ]
    smem, vmem = [], []
    for (k, r, pk, u, b), tp, seg in zip(tables, table_plans, segs):
        sched, drain, *blocks = pack_flow_operands(
            seg, k, r, pk, u, b, n_counters=tp.n_counters,
            n_ewma=tp.n_ewma, w_pad=_snap(int(r.shape[1]), tile))
        smem += [sched, drain]
        vmem += blocks
    seg0 = segs[0]
    # the suffix runs in the first table's sorted order: row p there is
    # row inv_t[order_0[p]] of table t's
    smem += [smem_vec(seg.inv[seg0.order], b_pad) for seg in segs[1:]]
    vmem += list(suffix_arrays)

    pk0 = tables[0][2]
    if mit_plan is not None:
        mseg = seg0 if mit_plan.shared_seg else segment_batch(
            hash_slot(pk0, int(mit_keys.shape[0])), valid,
            int(mit_keys.shape[0]))
        head = mit_regs[mseg.slot]
        vmem += [
            rows(head[:, :1], b_pad, 1),
            rows(head[:, 1:2], b_pad, 1),
            col(chain_fresh(mseg, pk0[mseg.order], mit_keys[mseg.slot]),
                b_pad),
        ]
        if not mit_plan.shared_seg:
            smem += [mseg.sched, smem_vec(mseg.drain, b_pad)]
            vmem += [col(mseg.rank, b_pad, fill=-1),
                     col(seg0.inv[mseg.order], b_pad)]

    plan = Plan(tables=table_plans, suffix=suffix_plan, mit=mit_plan)
    res = fused_flow_serve_padded(*smem, *vmem, plan=plan,
                                  interpret=interpret)
    outs = []
    for t, ((k, r, pk, _, _), seg) in enumerate(zip(tables, segs)):
        outs += scatter_tails(seg, k, r, pk, res[t][:B, :int(r.shape[1])])
    verd = res[-1][:B, 0]
    if mit_plan is not None:
        post = jnp.concatenate([res[nt][:B], res[nt + 1][:B]], axis=1)
        outs += scatter_tails(mseg, mit_keys, mit_regs, pk0, post)
        verd = verd[mseg.inv]          # action-table order -> arrival
    else:
        verd = verd[seg0.inv]          # first-table order -> arrival
    return tuple(outs) + (verd,)


def fused_flow_classify(
    keys: jax.Array,       # [S] int32 stored keys (-1 = empty)
    regs: jax.Array,       # [S, W] f32 register rows
    pkt_keys: jax.Array,   # [B] int32 per-packet flow keys (>= 0)
    upd: jax.Array,        # [B, C+E] f32 counter increments ++ EWMA values
    bins: jax.Array,       # [B, H] int32 absolute hist columns (-1 = none)
    valid: jax.Array,      # [B] int-ish; 0 = padding row, never applied
    w_stack: jax.Array,    # [L, lane, lane] packed layer weights
    b_stack: jax.Array,    # [L, lane] packed biases
    *,
    n_counters: int,
    n_ewma: int,
    alpha: float,
    mode: str,             # WindowStats readout: all | hist | raw (none)
    num_classes: int,
    lane: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The single-table MLP form -> (keys' [S], regs' [S, W], verdicts
    [B] int32 in arrival order), one kernel launch."""
    H = int(bins.shape[1]) if bins.ndim == 2 else 0
    tp = TablePlan(n_counters, n_ewma, H, float(alpha),
                   int(regs.shape[1]), mode)
    sp = SuffixPlan("mlp", num_classes, n_layers=int(w_stack.shape[0]),
                    lane=lane)
    k2, r2, verd = fused_flow_serve(
        [(keys, regs, pkt_keys, upd, bins)], valid, (tp,), sp,
        (w_stack, b_stack), interpret=interpret,
    )
    return k2, r2, verd
