"""In-pipeline mitigation: per-flow drop / rate-limit action registers.

Detection alone is half a data-plane ML pipeline; the paper's operators
act on verdicts at line rate.  This module is the action half: a second,
tiny register file (the ACTION TABLE) keyed by the same FNV flow key the
detection table uses, fed by the classifier's verdict stream.  Once a
flow accumulates ``threshold`` positive verdicts its slot is *marked*,
and every later packet of that flow is dropped (``mode="drop"``) or
rate-limited (``mode="rate_limit"``: every ``keep_every``-th packet
passes through and keeps being classified, the rest are dropped).

A dropped packet's verdict is replaced by the sentinel ``MITIGATED``
(-1) — by construction **no packet is ever both dropped and verdicted**,
and the packet that trips the threshold is itself verdicted, not dropped
(the state *before* a packet decides its fate), so the mitigation lag is
always >= 1 packet.

Layout (mirrors ``registers.FlowState``): stored keys [S] int32 with -1
= empty, register rows [S, 2] f32 — column 0 counts positive verdicts
(*hits*), column 1 counts packets since the slot was marked (*since*,
the rate-limit phase).  Same direct-indexed hash (``hash_slot``), same
evict-on-collision / last-writer-wins policy, same arrival-order
batch-scan semantics as the detection table — and the same honest SRAM
accounting (``MitigationSpec.sram_bytes`` is charged by
``feasibility.mitigation_report``).

The batch scan is ORDER-DEPENDENT (a later packet may evict an earlier
packet's slot), so the reference here runs as a ``fori_loop`` over the
batch — shared jnp code on every execution engine.  Under
``backend="pallas"`` the action table FOLDS INTO the fused flow launch
(``kernels/fused_flow.mitigation_phase``: the [hits, since] row rides
the same segmented lockstep-rounds + drain schedule as the detection
table, and the drop decision rewrites the verdict of the packet being
stepped), so
a mitigated pipeline reports ``"pallas-fused-flow"``; slots never
interact, so the fused phase is bit-identical to this scan by the same
per-slot decomposition that pins the flow tables.  When the rest of the
pipeline is outside the fused envelope, this scan serves as the split
fallback and ``StatefulPipeline`` reports the composite engine honestly
(``"mixed"``).  See docs/pipeline_ir.md#mitigation-contract.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flow_update.ref import hash_slot
from repro.flowstate.registers import FlowStateSpec, hash_slot_np

# verdict sentinel for a dropped packet: the packet never produced a
# verdict — the engine's output vocabulary becomes {MITIGATED} + classes
MITIGATED = -1

MITIGATION_MODES = ("drop", "rate_limit")

# action-table row layout: [hits, since]
MIT_WIDTH = 2


@dataclasses.dataclass(frozen=True)
class MitigationSpec:
    """Shape + policy of the per-flow action table.

    ``threshold`` positive verdicts (class ``attack_class``) mark a
    flow's slot; ``mode="drop"`` then drops every later packet,
    ``mode="rate_limit"`` passes every ``keep_every``-th packet through
    (it keeps being classified — the pass-through cadence is what lets a
    rate-limited flow keep feeding the detector)."""

    n_slots: int = 1024
    mode: str = "drop"
    threshold: int = 3
    keep_every: int = 8
    attack_class: int = 1

    def __post_init__(self):
        if self.n_slots < 2 or self.n_slots & (self.n_slots - 1):
            raise ValueError(
                f"n_slots must be a power of two >= 2, got {self.n_slots}"
            )
        if self.mode not in MITIGATION_MODES:
            raise KeyError(
                f"mode must be one of {MITIGATION_MODES}, got {self.mode!r}"
            )
        if self.threshold < 1:
            raise ValueError("threshold must be >= 1")
        if self.keep_every < 2:
            raise ValueError("keep_every must be >= 2 (1 would disable "
                             "rate limiting entirely)")

    @property
    def width(self) -> int:
        """Register words per action row ([hits, since])."""
        return MIT_WIDTH

    @property
    def sram_bytes(self) -> int:
        """Stored key + row words per slot — what feasibility charges."""
        return self.n_slots * (self.width + 1) * 4


def init_mitigation(spec: MitigationSpec) -> tuple[jax.Array, jax.Array]:
    """Fresh empty action table -> (mit_keys [S], mit_regs [S, 2])."""
    return (jnp.full((spec.n_slots,), -1, jnp.int32),
            jnp.zeros((spec.n_slots, MIT_WIDTH), jnp.float32))


@dataclasses.dataclass
class MitigatedFlowState:
    """Detection register file + action table, threaded as one state.

    The flow fields keep the ``FlowState`` names (``spec``/``keys``/
    ``regs``) so everything that reads a stateful engine's table — the
    sharded router, migrate paths, stats — works unchanged."""

    spec: FlowStateSpec
    keys: jax.Array        # [S] int32 detection table keys
    regs: jax.Array        # [S, W] f32 detection rows
    mit_spec: MitigationSpec
    mit_keys: jax.Array    # [Sm] int32 action-table keys, -1 = empty
    mit_regs: jax.Array    # [Sm, 2] f32 [hits, since]

    @property
    def occupied(self) -> int:
        return int(np.sum(np.asarray(self.keys) >= 0))

    @property
    def mitigated_flows(self) -> int:
        """Action-table slots currently marked (hits >= threshold)."""
        mk = np.asarray(self.mit_keys)
        hits = np.asarray(self.mit_regs)[:, 0]
        return int(np.sum((mk >= 0) & (hits >= self.mit_spec.threshold)))


def mitigate_update(
    mit_keys: jax.Array,   # [S] int32 stored keys (-1 = empty)
    mit_regs: jax.Array,   # [S, 2] f32 [hits, since]
    pkt_keys: jax.Array,   # [B] int32 flow key per packet
    verdicts: jax.Array,   # [B] int32 classifier verdicts
    valid: jax.Array,      # [B] 0 = padding row, skipped
    *,
    spec: MitigationSpec,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One batched action-table update -> (keys', regs', out_verdicts).

    Per packet, in arrival order: the slot's state BEFORE the packet
    decides — a marked slot drops (or rate-limits) the packet and its
    output verdict becomes ``MITIGATED``; an unmarked slot passes the
    classifier verdict through.  Then the row updates: ``hits`` grows by
    one when the verdict is ``attack_class`` (dropped packets still
    count — the detector already saw them), ``since`` counts packets
    while marked.  Padding rows never touch the table and keep their
    (meaningless) verdicts.  Traceable/jittable; shared by every
    execution engine, hence bit-identical across backends."""
    S = int(mit_keys.shape[0])
    B = int(pkt_keys.shape[0])
    pk = jnp.asarray(pkt_keys, jnp.int32)
    vd = jnp.asarray(verdicts, jnp.int32)
    ok = jnp.asarray(valid, jnp.int32) != 0
    slots = hash_slot(pk, S)
    thr = jnp.float32(spec.threshold)
    keep = jnp.float32(spec.keep_every)
    drop_mode = spec.mode == "drop"

    def body(p, carry):
        keys, regs, out = carry
        slot = slots[p]
        key = pk[p]
        stored = jax.lax.dynamic_slice(keys, (slot,), (1,))[0]
        row = jax.lax.dynamic_slice(regs, (slot, 0), (1, MIT_WIDTH))[0]

        # evict-on-collision: empty (-1) or different flow -> fresh row
        fresh = stored != key
        row0 = jnp.where(fresh, jnp.zeros_like(row), row)
        hits0, since0 = row0[0], row0[1]

        marked0 = hits0 >= thr
        if drop_mode:
            drop = marked0
        else:
            # pass every keep_every-th packet of a marked flow through
            drop = marked0 & (jnp.mod(since0, keep) != 0.0)
        v = vd[p]
        out_v = jnp.where(drop, jnp.int32(MITIGATED), v)

        hits1 = hits0 + (v == jnp.int32(spec.attack_class)).astype(
            jnp.float32)
        since1 = jnp.where(marked0, since0 + 1.0, 0.0)
        new_row = jnp.stack([hits1, since1])

        o = ok[p]
        keys = jax.lax.dynamic_update_slice(
            keys, jnp.where(o, key, stored)[None], (slot,))
        regs = jax.lax.dynamic_update_slice(
            regs, jnp.where(o, new_row, row)[None, :], (slot, 0))
        out = out.at[p].set(jnp.where(o, out_v, v))
        return keys, regs, out

    keys, regs, out = jax.lax.fori_loop(
        0, B, body,
        (jnp.asarray(mit_keys, jnp.int32),
         jnp.asarray(mit_regs, jnp.float32), vd),
    )
    return keys, regs, out


def migrate_mitigation(mit_keys, mit_regs, old_spec: MitigationSpec,
                       new_spec: MitigationSpec
                       ) -> tuple[jax.Array, jax.Array]:
    """Re-key the action table for a hot swap that CHANGES the mitigation
    spec — the same host-side control-plane scan as
    ``registers.migrate_state``: occupied rows re-hash into the new table
    in ascending slot order, colliding rows resolve last-writer-wins.
    The row layout is fixed ([hits, since]), so rows carry verbatim; a
    changed ``threshold``/``mode`` re-interprets the carried counts from
    the next packet on (a marked flow stays marked iff its carried hits
    clear the new threshold)."""
    del old_spec  # row layout is spec-independent; only n_slots re-keys
    keys = np.asarray(mit_keys)
    regs = np.asarray(mit_regs)
    out_k = np.full((new_spec.n_slots,), -1, np.int32)
    out_r = np.zeros((new_spec.n_slots, MIT_WIDTH), np.float32)
    occupied = np.flatnonzero(keys >= 0)      # ascending slot order
    slots = hash_slot_np(keys[occupied], new_spec.n_slots)
    for i, s in zip(occupied, slots):         # last-writer-wins collisions
        out_k[s] = keys[i]
        out_r[s] = regs[i]
    return jnp.asarray(out_k), jnp.asarray(out_r)
