"""Per-flow register file: the stateful half of a data-plane ML pipeline.

Real per-packet ML data planes (Taurus, Planter-style P4 targets) keep
per-flow registers — counters, EWMAs, windowed histograms — updated at line
rate, and classify on those registers instead of precomputed offline
features.  This module is that register file for the serving engine:

  * ``FlowStateSpec`` — the shape of one flow's state: a direct-indexed
    hash table with a FIXED slot count (power of two) whose rows hold
    ``n_counters`` accumulators, ``n_ewma`` exponential moving averages and
    one histogram section per entry of ``hist_sizes``;
  * ``FlowState`` — the live table: stored keys [S] (-1 = empty) plus
    register rows [S, W];
  * ``update_flows`` — one batched update through either execution engine
    (jnp scan reference, or the fused Pallas scatter/gather kernel in
    ``kernels/flow_update`` — bit-identical by construction).

Collision policy (see docs/pipeline_ir.md#flow-state-contract): slots are
direct-indexed by ``hash(key) & (S-1)``; a packet whose key differs from
the stored key EVICTS the resident flow — state resets to zero and the new
flow claims the slot (last-writer-wins).  This is the honest semantics of
a fixed-size switch register array: under slot pressure, long-lived flows
can be displaced, and accuracy degrades gracefully with table load rather
than the engine re-allocating memory mid-stream.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FlowStateSpec:
    """Shape of the per-flow register file.

    ``n_counters`` >= 1; counter 0 is by convention the packet count (the
    stage lowering always increments it by 1, and ``WindowStats`` uses it
    as the histogram normalizer).  ``hist_sizes`` lists the bin count of
    each histogram section; sections are laid out back to back after the
    EWMA block."""

    n_slots: int = 1024
    n_counters: int = 1
    n_ewma: int = 0
    hist_sizes: tuple = ()
    ewma_alpha: float = 0.125

    def __post_init__(self):
        if self.n_slots < 2 or self.n_slots & (self.n_slots - 1):
            raise ValueError(
                f"n_slots must be a power of two >= 2, got {self.n_slots}"
            )
        if self.n_counters < 1:
            raise ValueError("n_counters must be >= 1 (slot 0 = pkt count)")
        if any(int(h) < 1 for h in self.hist_sizes):
            raise ValueError("every histogram needs >= 1 bin")
        # shift-EWMA contract: a power-of-two alpha keeps both blend
        # products exact in f32, which is what makes the scan reference,
        # the segmented kernel and the fused kernel bit-identical no
        # matter how the compiler groups the multiply-adds (see
        # kernels.flow_update.ref.ewma_blend).
        a = float(self.ewma_alpha)
        if self.n_ewma and not (0.0 < a < 1.0 and math.frexp(a)[0] == 0.5):
            raise ValueError(
                "ewma_alpha must be a power of two in (0, 1) "
                f"(shift-EWMA contract), got {self.ewma_alpha}"
            )

    @property
    def width(self) -> int:
        """Register words per flow row (counters + EWMAs + hist bins)."""
        return self.n_counters + self.n_ewma + sum(self.hist_sizes)

    @property
    def hist_offsets(self) -> tuple:
        """Absolute start column of each histogram section."""
        offs, base = [], self.n_counters + self.n_ewma
        for h in self.hist_sizes:
            offs.append(base)
            base += int(h)
        return tuple(offs)

    @property
    def sram_bytes(self) -> int:
        """Table footprint: rows plus the stored-key word per slot — what
        feasibility charges against the target's register budget."""
        return self.n_slots * (self.width + 1) * 4


@dataclasses.dataclass
class FlowState:
    """The live register file; arrays are treated as immutable (every
    update returns a new FlowState over fresh buffers)."""

    spec: FlowStateSpec
    keys: jax.Array    # [S] int32 stored flow key, -1 = empty slot
    regs: jax.Array    # [S, W] f32 register rows

    @property
    def occupied(self) -> int:
        return int(np.sum(np.asarray(self.keys) >= 0))


def init_state(spec: FlowStateSpec) -> FlowState:
    return FlowState(
        spec,
        jnp.full((spec.n_slots,), -1, jnp.int32),
        jnp.zeros((spec.n_slots, spec.width), jnp.float32),
    )


@dataclasses.dataclass
class MultiFlowState:
    """Live state of a MULTI-TABLE stateful pipeline: several FlowKey /
    RegisterUpdate tables feeding one classifier (the multi-table DAG
    form), plus an optional mitigation action table.

    ``spec`` / ``keys`` / ``regs`` alias table 0 so single-table readers —
    the telemetry health probe, engine stats, reprs — keep working on the
    primary table; per-table access goes through the ``*_list`` tuples."""

    specs: tuple               # of FlowStateSpec, one per table
    keys_list: tuple           # of [S_t] int32 stored keys (-1 = empty)
    regs_list: tuple           # of [S_t, W_t] f32 register rows
    mit_spec: object = None    # mitigation.MitigationSpec | None
    mit_keys: jax.Array = None
    mit_regs: jax.Array = None

    @property
    def spec(self) -> FlowStateSpec:
        return self.specs[0]

    @property
    def keys(self) -> jax.Array:
        return self.keys_list[0]

    @property
    def regs(self) -> jax.Array:
        return self.regs_list[0]

    @property
    def occupied(self) -> int:
        """Occupied slots summed over every table."""
        return int(sum(np.sum(np.asarray(k) >= 0) for k in self.keys_list))

    @property
    def mitigated_flows(self) -> int:
        """Action-table slots currently marked (hits >= threshold)."""
        if self.mit_spec is None:
            return 0
        mk = np.asarray(self.mit_keys)
        hits = np.asarray(self.mit_regs)[:, 0]
        return int(np.sum((mk >= 0) & (hits >= self.mit_spec.threshold)))


def hash_slot_np(keys: np.ndarray, n_slots: int) -> np.ndarray:
    """Numpy twin of ``kernels.flow_update.ref.hash_slot`` — same Knuth
    multiplicative mix, same xor-fold — for host-side table migration.
    Pinned equal to the traceable form in tests/test_hot_swap.py."""
    with np.errstate(over="ignore"):
        h = np.asarray(keys).astype(np.uint32) * np.uint32(2654435761)
    h = h ^ (h >> np.uint32(16))
    return (h & np.uint32(n_slots - 1)).astype(np.int32)


def migrate_state(state: FlowState, new_spec: FlowStateSpec) -> FlowState:
    """The documented re-key path for a hot swap that CHANGES the spec
    (docs/pipeline_ir.md#hot-swap-contract).  Same-spec swaps never come
    here — the live table carries over bit-identically.

    Every occupied row is re-hashed into the new table (``hash_slot`` over
    ``new_spec.n_slots``), walking slots in ascending order with the table's
    own collision policy: two old flows landing on one new slot resolve
    last-writer-wins, exactly as live eviction would.  Register columns
    carry over section by section — the shared prefix of counters, the
    shared prefix of EWMAs, and each histogram section up to the smaller
    bin count — anything the new spec adds starts at zero, anything it
    drops is discarded.  This is a host-side control-plane operation (one
    table scan), not a per-packet path."""
    old = state.spec
    keys = np.asarray(state.keys)
    regs = np.asarray(state.regs)
    out_k = np.full((new_spec.n_slots,), -1, np.int32)
    out_r = np.zeros((new_spec.n_slots, new_spec.width), np.float32)

    # (old column, new column) pairs of the shared layout sections
    pairs: list[tuple[int, int]] = []
    for j in range(min(old.n_counters, new_spec.n_counters)):
        pairs.append((j, j))
    for j in range(min(old.n_ewma, new_spec.n_ewma)):
        pairs.append((old.n_counters + j, new_spec.n_counters + j))
    for h, (o_off, n_off) in enumerate(
        zip(old.hist_offsets, new_spec.hist_offsets)
    ):
        for j in range(min(old.hist_sizes[h], new_spec.hist_sizes[h])):
            pairs.append((o_off + j, n_off + j))
    o_cols = np.array([p[0] for p in pairs], np.int64)
    n_cols = np.array([p[1] for p in pairs], np.int64)

    occupied = np.flatnonzero(keys >= 0)      # ascending slot order
    slots = hash_slot_np(keys[occupied], new_spec.n_slots)
    for i, s in zip(occupied, slots):         # last-writer-wins collisions
        out_k[s] = keys[i]
        out_r[s] = 0.0
        out_r[s, n_cols] = regs[i, o_cols]
    return FlowState(new_spec, jnp.asarray(out_k), jnp.asarray(out_r))


def update_flows(
    state: FlowState,
    pkt_keys,              # [B] int32 flow key per packet (>= 0)
    upd,                   # [B, C+E] counter increments ++ EWMA values
    bins=None,             # [B, H] absolute hist columns (-1 = none)
    valid=None,            # [B] 0 = padding row, skipped
    *,
    backend: str = "interpret",
) -> tuple[FlowState, jax.Array]:
    """One batched register update -> (new state, per-packet feature rows).

    ``backend="pallas"`` runs the flow-update kernel (one launch; the
    table stays in HBM, only the batch's rows enter the kernel);
    ``"interpret"`` the jitted jnp scan.  Both are
    bit-identical (shared per-packet step) and preserve arrival order."""
    from repro.kernels import flow_update as fu

    spec = state.spec
    B = int(np.shape(pkt_keys)[0])
    if bins is None:
        bins = jnp.full((B, 1), -1, jnp.int32)
    if valid is None:
        valid = jnp.ones((B,), jnp.int32)
    fn = fu.flow_update if backend == "pallas" else fu.flow_update_ref
    keys, regs, feats = fn(
        state.keys, state.regs, jnp.asarray(pkt_keys, jnp.int32),
        jnp.asarray(upd, jnp.float32), jnp.asarray(bins, jnp.int32),
        jnp.asarray(valid, jnp.int32),
        n_counters=spec.n_counters, n_ewma=spec.n_ewma,
        alpha=spec.ewma_alpha,
    )
    return FlowState(spec, keys, regs), feats
