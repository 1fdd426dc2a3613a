"""Pallas kernel: batched flow-state update in ONE launch.

The jnp reference (ref.py) walks the batch packet-by-packet — the update is
order-dependent (EWMAs are non-commutative, collisions evict), so a naive
vectorization is wrong.  This kernel exploits the one independence the
semantics do give: REGISTER SLOTS NEVER INTERACT.  Each slot's final state
is a function of its own packets' subsequence only, so the sequential loop
factorizes into per-slot chains.

The wrapper (ops.py) pre-SEGMENTS the batch in an XLA prelude: a stable
sort by slot turns every per-slot chain into a contiguous run (stable, so
per-slot arrival order is preserved).  In that SORTED order the state a
packet sees is the row its chain predecessor — the previous sorted row —
left behind, or, for a chain head (``rank == 0``), the table row itself.
The prelude therefore hands the kernel, per sorted packet:

  * ``init`` — the table row at the packet's slot (read by chain heads);
  * ``fresh`` — whether the packet evicts: a head's stored key differs
    from its own, or a mid-chain key change (two flows sharing a slot);
  * ``add`` / ``val`` — the full-width update terms (counter increments
    plus histogram bumps; EWMA values);
  * ``rank`` — its position in the chain (``-1`` for padding rows).

The table itself never enters the kernel: its rows are gathered by the
prelude and the chain tails are scattered back by the epilogue, so the
table lives in HBM and the kernel's VMEM working set depends on the batch
only.  The kernel runs an exact two-phase walk over ``[B, W]`` rows:

  1. LOCKSTEP ROUNDS — round r applies, simultaneously for every chain,
     the packet of rank r: its predecessor row is the previous sorted row
     (one sublane rotate of the whole state block) or ``init``.  Within a
     round all targets are distinct chains; across rounds each chain sees
     its packets in arrival order.  Runs ``min(max_rank + 1, PAR_ROUNDS)``
     rounds.
  2. SEQUENTIAL DRAIN — the deep-chain remainder (``rank >= PAR_ROUNDS``)
     replays one packet at a time in sorted order, each a scalar-indexed
     ``[1, W]`` row load and store (positions from SMEM).

Both phases apply the same elementwise f32 expressions, in the same order,
as the reference's ``_packet_step``, so state, features and verdicts are
**bit-identical** to ``flow_update_ref`` by the per-slot decomposition —
the conformance suite pins this over random collision-heavy batches.  The
output rows are the post-update rows in SORTED order: they are at once
each packet's feature row and, at each chain's tail, the slot's new state.

``walk`` is the schedule over refs, shared with the fused stateful kernel
(kernels/fused_flow), which runs the identical flow update and the
mitigation action table on it.

Grid: (1,) — rounds are a sequential dependency chain; every operand is
one VMEM block.  Schedule scalars and drain positions ride in SMEM
(scalar prefetch).  ``vmem_bytes`` is the working set feasibility checks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flow_update.ref import ewma_blend

LANE = 128
SUBLANE = 8
# chain ranks retired by the vectorized lockstep rounds before the
# schedule switches to the sequential drain (one round costs ~a dozen
# [B, W] vector ops, one drained packet ~a dozen [1, W] ops)
PAR_ROUNDS = 8
# on-chip memory a launch may claim: the compiler's scoped limit is
# raised to the kernel's own estimate, capped here (a v5e TensorCore
# has 128 MiB of VMEM)
VMEM_LIMIT = 96 * 2**20


def walk(sched_ref, drain_ref, rank, state_refs, init, step,
         extra_refs=()):
    """Run the lockstep-rounds + drain schedule over sorted-order refs.

    ``sched_ref`` (SMEM) holds ``[n_rounds, n_drain]``; ``drain_ref``
    (SMEM) the sorted positions of the ``rank >= PAR_ROUNDS`` packets in
    sorted order; ``rank`` is the ``[B, 1]`` chain-rank column.
    ``state_refs`` are ``[B, w]`` refs holding each packet's post-update
    state (zero-initialised by the caller), ``init`` the rows a chain
    head starts from.  ``step(prev, rows)`` maps the predecessor state
    (list of arrays) and a row selector (``slice(None)`` for a whole
    round, ``pl.ds(p, 1)`` for one drained packet) to ``(new_state,
    extras)``; extras land in ``extra_refs`` at the same rows."""
    def round_body(r, carry):
        cur = [s[...] for s in state_refs]
        prev = [jnp.where(rank == 0, i, pltpu.roll(c, 1, 0))
                for i, c in zip(init, cur)]
        new, extras = step(prev, slice(None))
        hit = rank == r
        for s, c, n in zip(state_refs, cur, new):
            s[...] = jnp.where(hit, n, c)
        for e, v in zip(extra_refs, extras):
            e[...] = jnp.where(hit, v, e[...])
        return carry

    jax.lax.fori_loop(0, sched_ref[0], round_body, 0)

    def drain_body(i, carry):
        p = drain_ref[i]
        prev = [s[pl.ds(p - 1, 1), :] for s in state_refs]
        new, extras = step(prev, pl.ds(p, 1))
        for s, n in zip(state_refs, new):
            s[pl.ds(p, 1), :] = n
        for e, v in zip(extra_refs, extras):
            e[pl.ds(p, 1), :] = v
        return carry

    jax.lax.fori_loop(0, sched_ref[1], drain_body, 0)


def flow_phase(sched_ref, drain_ref, init_ref, add_ref, val_ref, rank_ref,
               fresh_ref, x_ref, *, n_counters: int, n_ewma: int,
               alpha: float):
    """The register update of one table over sorted-order refs; leaves
    each packet's post-update row in ``x_ref`` (zero for padding)."""
    W = x_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    m_ewma = (col >= n_counters) & (col < n_counters + n_ewma)

    def step(prev, rows):
        fresh = fresh_ref[rows, :] != 0
        val = val_ref[rows, :]
        # the reference's _packet_step, elementwise: evict-on-collision,
        # EWMA set-or-blend, counter/hist increments (disjoint columns,
        # so one additive term per column)
        row0 = jnp.where(fresh, jnp.zeros_like(prev[0]), prev[0])
        ewma = jnp.where(fresh, val, ewma_blend(row0, val, alpha))
        return [jnp.where(m_ewma, ewma, row0) + add_ref[rows, :]], []

    x_ref[...] = jnp.zeros_like(x_ref)
    walk(sched_ref, drain_ref, rank_ref[...], [x_ref], [init_ref[...]],
         step)


def block(shape):
    """Whole-array VMEM block for a grid=(1,) launch (index maps take the
    scalar-prefetch refs as trailing arguments)."""
    return pl.BlockSpec(shape, lambda i, *_, _n=len(shape): (0,) * _n)


def compiler_params(nbytes: int):
    """Mosaic parameters: the scoped-VMEM limit raised to the launch's
    own working-set estimate (``nbytes``), within ``VMEM_LIMIT``."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(2 * nbytes, 32 * 2**20), VMEM_LIMIT)))


@functools.partial(
    jax.jit, static_argnames=("n_counters", "n_ewma", "alpha", "interpret")
)
def flow_update_padded(
    sched: jax.Array,       # [2] int32 (n_rounds, n_drain)          SMEM
    drain: jax.Array,       # [B_pad] int32 drain positions, sorted  SMEM
    init: jax.Array,        # [B_pad, W_pad] f32 table row at the slot
    add: jax.Array,         # [B_pad, W_pad] f32 counter/hist increments
    val: jax.Array,         # [B_pad, W_pad] f32 EWMA values
    rank: jax.Array,        # [B_pad, 1] int32 chain rank (-1 = padding)
    fresh: jax.Array,       # [B_pad, 1] int32 1 = evict before update
    *,
    n_counters: int,
    n_ewma: int,
    alpha: float,
    interpret: bool = False,
) -> jax.Array:
    """-> post-update rows [B_pad, W_pad] f32 in SORTED order."""
    b_pad, w_pad = init.shape
    return pl.pallas_call(
        functools.partial(flow_phase, n_counters=n_counters,
                          n_ewma=n_ewma, alpha=alpha),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[block(init.shape), block(add.shape),
                      block(val.shape), block(rank.shape),
                      block(fresh.shape)],
            out_specs=block((b_pad, w_pad)),
        ),
        out_shape=jax.ShapeDtypeStruct((b_pad, w_pad), jnp.float32),
        compiler_params=compiler_params(vmem_bytes(w_pad, b_pad)),
        interpret=interpret,
    )(sched, drain, init, add, val, rank, fresh)


def vmem_bytes(width: int, batch: int = 256) -> int:
    """VMEM working set of one table's flow phase (feasibility input),
    at TPU tiling: three [B, W] operand blocks (init, add, val) and the
    output block, every row lane-padded to 128, plus the two [B, 1] int
    columns (rank, fresh) padded to a full lane tile, all double-buffered
    by the pipeline.  The table is not resident — it stays in HBM."""
    w_pad = -(-width // LANE) * LANE
    b_pad = -(-batch // SUBLANE) * SUBLANE
    return 2 * (4 * b_pad * w_pad + 2 * b_pad * LANE) * 4
