"""Public op: batched flow-register update, one kernel launch.

``flow_update(keys, regs, pkt_keys, upd, bins, valid)`` segments the batch
by slot, gathers each packet's slot row, launches the Pallas kernel
(interpret=True on CPU — the TPU path is the same kernel compiled by
Mosaic), scatters each chain's last row back into the table and restores
arrival order.  This is the executable artifact the Pallas serving backend
(core.pallas_backend.lower_stateful_pallas) emits for the stateful stage
prefix ``FlowKey -> RegisterUpdate``.

The table stays in HBM: the prelude gathers the ``[B]`` rows the batch
touches and the epilogue scatters ``[B]`` rows back (in place when the
serving step donates the table), so the kernel's VMEM working set depends
on the batch alone.  ``MAX_SLOTS`` is therefore an HBM bound, not a VMEM
one; a table outside the envelope is refused here and declined, with a
reason, by the lowering (``core.pallas_backend``) — it never silently
takes the reference path.  Padding is self-masking: padded register
columns start zero and are never addressed (absolute hist columns < W,
counter/EWMA sections are static slices), so the real columns are
bit-identical to the unpadded reference.

Slot segmentation (``segment_batch``, shared with kernels/fused_flow): a
STABLE argsort by slot makes every per-slot chain contiguous while
preserving per-slot arrival order, so each packet's rank within its chain
falls out of a cumulative max in O(B log B) — no [B, B] intermediates.
The inverse permutation restores arrival-order feature rows; the table
update itself is order-independent across slots, so sorting never changes
the final state.  (``telemetry.flow_health`` flags drain-heavy batches —
more than 7/8 of live packets deeper than ``PAR_ROUNDS`` — as a
traffic-shape signal; it is not a routing decision.)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.flow_update.kernel import (
    LANE,
    PAR_ROUNDS,
    SUBLANE,
    flow_update_padded,
)
from repro.kernels.flow_update.ref import hash_slot

# kernel envelope.  The table lives in HBM (only the batch's rows move),
# so no slot count is refused for VMEM: the flow-ddos serving step
# (28-word rows, batch 512) compiles for one v5e up to 2^26 slots, 8.25
# GiB updated in place, and 2^27 exceed the chip's HBM
# (tests/test_tpu_compile.py pins both).  MAX_SLOTS caps tables at the
# largest size served and checked on the chip (chip_smoke.py fills a
# quarter of it; traffic.make_stream draws flow ids from 2^20); 2^20
# slots of the widest row are 1 GiB.
MAX_SLOTS = 1 << 20
MAX_WIDTH = 256
MAX_HISTS = 8


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _snap(n: int, tile: int) -> int:
    return max(tile, -(-n // tile) * tile)


def envelope_reason(n_slots: int, width: int, n_hists: int) -> str | None:
    """Why a table is outside the flow kernels' envelope (None = inside)."""
    if n_slots > MAX_SLOTS:
        return f"flow table of {n_slots} slots exceeds MAX_SLOTS={MAX_SLOTS}"
    if width > MAX_WIDTH:
        return f"register width {width} exceeds MAX_WIDTH={MAX_WIDTH}"
    if n_hists > MAX_HISTS:
        return f"{n_hists} histograms exceed MAX_HISTS={MAX_HISTS}"
    return None


class Segments(NamedTuple):
    """Slot-segmented batch layout (entries in SORTED order except
    ``order``/``inv``, which map between arrival and sorted order)."""

    order: jax.Array       # [B] arrival index of sorted position i
    inv: jax.Array         # [B] sorted position of arrival index p
    slot: jax.Array        # [B] table slot of each sorted packet
    rank: jax.Array        # [B] position within the slot's chain (-1 = pad)
    tail: jax.Array        # [B] bool: last live packet of its chain
    drain: jax.Array       # [B] sorted positions with rank >= PAR_ROUNDS
    sched: jax.Array       # [2] (lockstep rounds, drained packets)


def segment_batch(slot: jax.Array, valid: jax.Array, n_slots: int, *,
                  par_rounds: int = PAR_ROUNDS) -> Segments:
    """Stable-sort the batch by slot and derive the kernel's schedule.

    Stability preserves per-slot arrival order, so ranks — and therefore
    the final table state — are exactly those of the arrival-order walk.
    Invalid rows sort last (keyed ``n_slots``), carry rank -1 and never
    start, extend or end a chain.  Runs as part of the jitted step."""
    B = slot.shape[0]
    live = valid != 0
    pos = jnp.arange(B, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(live, slot, n_slots), stable=True)
    slot_s = slot[order]
    live_s = live[order]

    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), slot_s[1:] != slot_s[:-1]]
    ) & live_s
    # rank = distance from the most recent chain head (live rows only)
    rank = pos - jax.lax.cummax(jnp.where(is_new, pos, 0))
    rank = jnp.where(live_s, rank, -1)
    nxt_ends = jnp.concatenate([is_new[1:] | ~live_s[1:],
                                jnp.ones((1,), bool)])
    tail = live_s & nxt_ends
    inv = jnp.zeros(B, jnp.int32).at[order].set(pos)

    # deep packets, compacted in sorted order (per chain that extends the
    # round order exactly)
    deep = rank >= par_rounds
    csum = jnp.cumsum(deep.astype(jnp.int32))
    drain = jnp.zeros(B, jnp.int32).at[
        jnp.where(deep, csum - 1, B)].set(pos, mode="drop")
    n_rounds = jnp.clip(jnp.max(rank) + 1, 0, par_rounds)
    sched = jnp.stack([n_rounds, csum[-1]]).astype(jnp.int32)
    return Segments(order.astype(jnp.int32), inv, slot_s, rank, tail,
                    drain, sched)


def padded_rows(batch: int) -> int:
    """Kernel row count for a batch: the 8-row sublane tile."""
    return _snap(batch, SUBLANE)


def chain_fresh(seg: Segments, keys_s: jax.Array, stored: jax.Array):
    """[B] bool: does each sorted packet evict?  A chain head compares the
    key stored in its slot, a later packet its chain predecessor's key
    (which the predecessor just wrote) — ``keys_s`` is the sorted packet
    keys, ``stored`` the table key at each sorted packet's slot."""
    prev = jnp.concatenate([keys_s[:1], keys_s[:-1]])
    return jnp.where(seg.rank == 0, stored != keys_s, prev != keys_s)


def col(vals, b_pad: int, fill: int = 0):
    """[B] ints -> [b_pad, 1] int32 kernel column."""
    v = jnp.asarray(vals, jnp.int32)[:, None]
    return jnp.pad(v, ((0, b_pad - v.shape[0]), (0, 0)),
                   constant_values=fill)


def rows(x, b_pad: int, w_pad: int):
    """[B, w] -> zero-padded [b_pad, w_pad] f32 kernel block."""
    x = jnp.asarray(x, jnp.float32)
    return jnp.pad(x, ((0, b_pad - x.shape[0]), (0, w_pad - x.shape[1])))


def smem_vec(vals, b_pad: int):
    """[B] ints -> [b_pad] int32 SMEM vector."""
    v = jnp.asarray(vals, jnp.int32)
    return jnp.pad(v, (0, b_pad - v.shape[0]))


def pack_flow_operands(seg: Segments, keys, regs, pkt_keys, upd, bins, *,
                       n_counters: int, n_ewma: int, w_pad: int):
    """One table's kernel operands, in sorted order: (sched, drain) for
    SMEM, then (init, add, val, rank, fresh).  ``add`` folds counter
    increments and histogram one-hots — disjoint columns, so each column
    sums at most one nonzero term and the fold is exact; ``val`` holds the
    EWMA set-values at their columns."""
    B = pkt_keys.shape[0]
    b_pad = padded_rows(B)
    C, E = n_counters, n_ewma
    o = seg.order
    pk_s = pkt_keys[o]
    u = upd[o]
    b = bins[o]
    colid = jax.lax.broadcasted_iota(jnp.int32, (B, w_pad), 1)
    add = jnp.pad(u[:, :C], ((0, 0), (0, w_pad - C)))
    for j in range(b.shape[1]):                  # static unroll per hist
        add = add + (colid == b[:, j:j + 1]).astype(jnp.float32)
    val = jnp.pad(u[:, C:C + E], ((0, 0), (C, w_pad - C - E)))
    fresh = chain_fresh(seg, pk_s, keys[seg.slot])
    return (
        seg.sched,
        smem_vec(seg.drain, b_pad),
        rows(regs[seg.slot], b_pad, w_pad),
        rows(add, b_pad, w_pad),
        rows(val, b_pad, w_pad),
        col(seg.rank, b_pad, fill=-1),
        col(fresh, b_pad),
    )


def scatter_tails(seg: Segments, keys, regs, pkt_keys, x):
    """Write each chain's last post-update row (and its key) home: the
    table after the batch.  ``x`` is the kernel's [B, W] sorted rows."""
    S = keys.shape[0]
    tgt = jnp.where(seg.tail, seg.slot, S)
    keys = keys.at[tgt].set(pkt_keys[seg.order], mode="drop")
    regs = regs.at[tgt].set(x, mode="drop")
    return keys, regs


def flow_update(
    keys: jax.Array,       # [S] int32 stored keys (-1 = empty)
    regs: jax.Array,       # [S, W] f32 register rows
    pkt_keys: jax.Array,   # [B] int32 per-packet flow keys (>= 0)
    upd: jax.Array,        # [B, C+E] f32 counter increments ++ EWMA values
    bins: jax.Array,       # [B, H] int32 absolute hist columns (-1 = none)
    valid: jax.Array,      # [B] int-ish; 0 = padding row, never applied
    *,
    n_counters: int,
    n_ewma: int,
    alpha: float,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (keys' [S], regs' [S, W], feats [B, W]), one kernel launch.

    Bit-identical to ``flow_update_ref`` (the same per-packet
    arithmetic); arrival order within the batch preserved; see the
    flow-state contract in docs/pipeline_ir.md for the eviction/ordering
    guarantees.  Raises ``ValueError`` outside the kernel envelope."""
    if interpret is None:
        interpret = not _on_tpu()
    S, W = regs.shape
    B = int(pkt_keys.shape[0])
    bins = jnp.asarray(bins, jnp.int32)
    if bins.ndim != 2:
        bins = jnp.full((B, 0), -1, jnp.int32)
    reason = envelope_reason(S, W, int(bins.shape[1]))
    if reason:
        raise ValueError(reason)
    if B == 0:
        return keys, regs, jnp.zeros((0, W), jnp.float32)
    # CPU interpret mode snaps pads to 8-wide tiles; TPU pads the last dim
    # to the full 128 lane.
    w_pad = _snap(W, 8 if interpret else LANE)
    keys = jnp.asarray(keys, jnp.int32)
    regs = jnp.asarray(regs, jnp.float32)
    pkt_keys = jnp.asarray(pkt_keys, jnp.int32)

    # segment ONCE: the layout IS the kernel's schedule.  Padding rows
    # (valid=0) are excluded, so a ragged tail cannot fake a deep chain.
    seg = segment_batch(hash_slot(pkt_keys, S), jnp.asarray(valid), S)
    ops = pack_flow_operands(
        seg, keys, regs, pkt_keys, jnp.asarray(upd, jnp.float32), bins,
        n_counters=n_counters, n_ewma=n_ewma, w_pad=w_pad,
    )
    x = flow_update_padded(
        *ops, n_counters=n_counters, n_ewma=n_ewma, alpha=float(alpha),
        interpret=interpret,
    )[:B, :W]
    keys, regs = scatter_tails(seg, keys, regs, pkt_keys, x)
    # rows come back in sorted order: inverse-permute to arrival order
    return keys, regs, x[seg.inv]
