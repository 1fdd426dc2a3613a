"""Plain reference of the flow-serving semantics, in numpy.

It imports nothing of the program.  It follows the flow-state and
mitigation contracts as documented (``docs/pipeline_ir.md``):

* flow key: the packet's key columns rounded to int32 and FNV-folded
  (``key = key * 16777619 ^ v`` from 0), sign bit cleared;
* slot: ``h = key * 2654435761 (mod 2**32); h ^= h >> 16; h & (S - 1)``;
  on four chips the shard is ``((key * 0x9E3779B1 mod 2**32) >> 16) % D``;
* per packet, in arrival order within its slot: a stored key other than
  the packet's evicts (the row restarts from zero); counter 0 counts
  packets, counter ``1 + j`` adds column ``counter_cols[j]``; EWMA ``j``
  takes its column's value on a fresh row and ``(r - r*a) + v*a`` after
  that; each histogram adds 1 at ``searchsorted(edges, v)`` (left);
* readout: counters and EWMAs as they are, histograms over
  ``max(count, 1)``; the classifier suffix (``bench/suffix/<kind>.py``)
  turns the readout into class scores and a verdict;
* mitigation: a second table on the same key, ``[hits, since]``; the
  row before a packet decides (``hits >= threshold`` drops it as
  ``MITIGATED``), then ``hits`` counts the attack verdicts.

Everything runs in float32 (the configuration's stated precision) except
``dtype=bfloat16``, the control.  Slots never interact, so the replay
walks a sample of slots in lockstep by arrival rank.
"""

from __future__ import annotations

import dataclasses

import numpy as np

MITIGATED = -1
FNV_PRIME = np.uint32(16777619)
SLOT_MULT = np.uint32(2654435761)
SHARD_MULT = np.uint32(0x9E3779B1)


def flow_keys(rows: np.ndarray, key_cols=(0,)) -> np.ndarray:
    """[n, F] packet rows -> [n] int64 flow keys."""
    key = np.zeros(len(rows), np.uint32)
    with np.errstate(over="ignore"):
        for c in key_cols:
            v = np.round(rows[:, c]).astype(np.int32).astype(np.uint32)
            key = key * FNV_PRIME ^ v
    return (key & np.uint32(0x7FFFFFFF)).astype(np.int64)


def slot_of(keys: np.ndarray, n_slots: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = keys.astype(np.uint32) * SLOT_MULT
    h = h ^ (h >> np.uint32(16))
    return (h & np.uint32(n_slots - 1)).astype(np.int64)


def shard_of(keys: np.ndarray, n_shards: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        mixed = keys.astype(np.uint32) * SHARD_MULT
    return ((mixed >> np.uint32(16)) % np.uint32(n_shards)).astype(np.int64)


def group_of(keys: np.ndarray, n_slots: int, n_shards: int) -> np.ndarray:
    """Table row a packet lands in: ``shard * n_slots + slot``."""
    g = slot_of(keys, n_slots)
    if n_shards > 1:
        g = g + shard_of(keys, n_shards) * n_slots
    return g


@dataclasses.dataclass(frozen=True)
class Registers:
    """The register layout a configuration states."""

    counter_cols: tuple        # packet columns of counters 1..
    ewma_cols: tuple
    hist_cols: tuple
    hist_edges: tuple          # float32 edges per histogram
    alpha: float

    @property
    def n_counters(self) -> int:
        return 1 + len(self.counter_cols)

    @property
    def head(self) -> int:
        return self.n_counters + len(self.ewma_cols)

    @property
    def width(self) -> int:
        return self.head + sum(len(e) + 1 for e in self.hist_edges)


def flow_registers(prefix: dict) -> Registers:
    """The configuration's register file: packet and byte counters, EWMAs
    of length and gap, histograms of length (linear bins to 1500 B) and of
    gap (geometric bins from 1e-4 to 120 s)."""
    pl, ib = int(prefix["pl_bins"]), int(prefix["ipt_bins"])
    return Registers(
        counter_cols=(1,), ewma_cols=(1, 2), hist_cols=(1, 2),
        hist_edges=(np.linspace(0.0, 1500.0, pl + 1)[1:-1].astype(np.float32),
                    np.geomspace(1e-4, 120.0, ib + 1)[1:-1].astype(np.float32)),
        alpha=float(prefix["ewma_alpha"]))


def lockstep_order(groups: np.ndarray):
    """Stable order by group, and each packet's arrival rank in its group."""
    order = np.argsort(groups, kind="stable")
    g = groups[order]
    new = np.ones(len(g), bool)
    new[1:] = g[1:] != g[:-1]
    starts = np.flatnonzero(new)
    seg = np.cumsum(new) - 1
    rank = np.arange(len(g)) - starts[seg]
    return order, rank


def replay_registers(rows: np.ndarray, keys: np.ndarray, groups: np.ndarray,
                     reg: Registers, dtype=np.float32):
    """Run the register file over the packets of a sample of table rows.

    ``rows`` [n, F] are the packets in arrival order, ``groups`` the table
    row each lands in.  Returns (post-update row of every packet [n, W],
    final stored key per distinct group, final row per distinct group,
    the distinct groups in ascending order)."""
    n = len(rows)
    W = reg.width
    uniq, gidx = np.unique(groups, return_inverse=True)
    tkeys = np.full(len(uniq), -1, np.int64)
    trows = np.zeros((len(uniq), W), dtype)
    post = np.zeros((n, W), dtype)
    a = dtype(reg.alpha)
    C, E = reg.n_counters, len(reg.ewma_cols)

    inc = np.ones((n, C), dtype)
    for j, c in enumerate(reg.counter_cols):
        inc[:, 1 + j] = rows[:, c].astype(dtype)
    val = np.stack([rows[:, c] for c in reg.ewma_cols], 1).astype(dtype) \
        if E else np.zeros((n, 0), dtype)
    bins = []
    off = reg.head
    for c, e in zip(reg.hist_cols, reg.hist_edges):
        bins.append(np.searchsorted(e, rows[:, c].astype(np.float32),
                                    side="left") + off)
        off += len(e) + 1
    bins = np.stack(bins, 1) if bins else np.zeros((n, 0), np.int64)

    order, rank = lockstep_order(gidx)
    for r in range(int(rank.max(initial=-1)) + 1):
        p = order[rank == r]               # at most one packet per group
        g = gidx[p]
        fresh = tkeys[g] != keys[p]
        row = np.where(fresh[:, None], dtype(0), trows[g])
        row[:, :C] = row[:, :C] + inc[p]
        old = row[:, C:C + E]
        blend = (old - old * a) + val[p] * a
        row[:, C:C + E] = np.where(fresh[:, None], val[p], blend)
        for j in range(bins.shape[1]):
            row[np.arange(len(p)), bins[p, j]] += dtype(1)
        trows[g] = row
        tkeys[g] = keys[p]
        post[p] = row
    return post, tkeys, trows, uniq


def readout(post: np.ndarray, reg: Registers):
    """Post-update rows -> classifier input, histograms over the count;
    with the inputs one float32 step below and above it where a division
    made them: the chip's float32 division is exact to one step (ulp),
    not correctly rounded, so those are the inputs it may give."""
    denom = np.maximum(post[:, :1], post.dtype.type(1))
    z = np.concatenate([post[:, :reg.head], post[:, reg.head:] / denom], 1)
    if z.dtype != np.float32:
        return z, z, z
    lo, hi = z.copy(), z.copy()
    lo[:, reg.head:] = np.nextafter(z[:, reg.head:], np.float32(-np.inf))
    hi[:, reg.head:] = np.nextafter(z[:, reg.head:], np.float32(np.inf))
    return z, lo, hi


def replay_mitigation(keys: np.ndarray, groups: np.ndarray,
                      verdicts: np.ndarray, spec: dict, dtype=np.float32,
                      either=None):
    """The action table over the classifier's verdicts, same sample ->
    (final verdicts, final key per group, final [hits, since] per group,
    the highest ``hits`` each group may hold).

    ``either`` marks packets whose classifier verdict may be the attack
    class or not (a division one step off); while a flow is dropped such a
    packet may or may not count a hit, so ``hits`` becomes a range.  A
    packet that is not dropped keeps the verdict it was given."""
    uniq, gidx = np.unique(groups, return_inverse=True)
    tkeys = np.full(len(uniq), -1, np.int64)
    trows = np.zeros((len(uniq), 2), dtype)
    hits_hi = np.zeros(len(uniq), dtype)
    out = verdicts.astype(np.int64).copy()
    if either is None:
        either = np.zeros(len(keys), bool)
    thr = dtype(spec["threshold"])
    keep = int(spec.get("keep_every", 8))
    drop_mode = spec.get("mode", "drop") == "drop"
    attack = int(spec.get("attack_class", 1))
    order, rank = lockstep_order(gidx)
    for r in range(int(rank.max(initial=-1)) + 1):
        p = order[rank == r]
        g = gidx[p]
        fresh = tkeys[g] != keys[p]
        h0 = np.where(fresh, dtype(0), trows[g, 0])
        h0_hi = np.where(fresh, dtype(0), hits_hi[g])
        s0 = np.where(fresh, dtype(0), trows[g, 1])
        marked = h0 >= thr
        if drop_mode:
            drop = marked
        else:
            drop = marked & (s0.astype(np.int64) % keep != 0)
        v = verdicts[p]
        out[p] = np.where(drop, MITIGATED, v)
        hit = (v == attack).astype(dtype)
        maybe = (drop & either[p]).astype(dtype)
        trows[g, 0] = h0 + hit * (dtype(1) - maybe)
        hits_hi[g] = h0_hi + np.maximum(hit, maybe)
        trows[g, 1] = np.where(marked, s0 + dtype(1), dtype(0))
        tkeys[g] = keys[p]
    return out, tkeys, trows, uniq, hits_hi
