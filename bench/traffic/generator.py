"""The one traffic generator: a mix file of parameters -> one lap of packets.

A mix (``bench/traffic/<name>.json``) describes datacenter flow traffic by
its parameters alone; this module turns it into packets, vectorised, from
``--seed``.  Packet rows have the serving path's four columns
(``flow_id, pkt_len, ipt_s, dst_port``, float32).

Background traffic runs on ``flows_active`` concurrent *lanes*: every
background packet picks a lane uniformly, and a lane's packets belong to
one flow after another (a finished flow is replaced by a fresh id).  A
flow's kind (``kinds``: web, bulk, chatty, with their flow shares, packet
counts, bimodal sizes, gaps and ports, plus ``attack`` flows sized to a
packet share) fixes its packet count and the size, gap and port of each
packet.  With many lanes nearly every packet of a batch has its own slot.
An optional ``flood`` takes a share of all packets from ``flows``
concurrent flood flows of ``flow_pkts`` packets each, so every batch holds
deep same-flow chains.

One lap of ``lap_per_flow * flows_active`` packets is built in set-up and
replayed: lap ``j`` adds ``j * id_span`` to every flow id (modulo
``2**id_bits``, exact in float32), so no id repeats across the first
``2**id_bits / id_span`` laps.  A flow that is open at the end of a lap is
cut there; the next lap starts its lanes with fresh flows.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

COLUMNS = ("flow_id", "pkt_len", "ipt_s", "dst_port")
MIN_LEN, MAX_LEN = 40.0, 1500.0


@dataclasses.dataclass
class Lap:
    """One lap of packets and how to replay it."""

    packets: np.ndarray        # [N, 4] float32, COLUMNS order
    id_span: int               # id offset between consecutive laps
    id_bits: int
    flood: np.ndarray          # [N] bool: the packet belongs to a flood flow

    @property
    def size(self) -> int:
        return len(self.packets)

    @property
    def max_laps(self) -> int:
        """Laps replayed before an id could repeat."""
        return (1 << self.id_bits) // self.id_span

    def lap_ids(self, lap: int, ids: np.ndarray) -> np.ndarray:
        """Flow ids of lap ``lap`` from lap 0's ids (float32 exact)."""
        mask = (1 << self.id_bits) - 1
        return ((ids.astype(np.int64) + lap * self.id_span) & mask
                ).astype(np.float32)

    def take(self, start: int, n: int) -> np.ndarray:
        """Rows ``[start, start + n)`` of the endless replay (a copy)."""
        out = np.empty((n, 4), np.float32)
        got = 0
        while got < n:
            lap, off = divmod(start + got, self.size)
            k = min(n - got, self.size - off)
            part = out[got:got + k]
            part[:] = self.packets[off:off + k]
            if lap:
                part[:, 0] = self.lap_ids(lap, part[:, 0])
            got += k
        return out

    def take_rows(self, index: np.ndarray) -> np.ndarray:
        """Rows at arbitrary replay positions (a copy)."""
        lap, off = np.divmod(np.asarray(index, np.int64), self.size)
        rows = self.packets[off].copy()
        rows[:, 0] = self.lap_ids(lap, rows[:, 0])
        return rows

    def flow_ids(self, start: int, n: int) -> np.ndarray:
        """int64 flow ids of rows ``[start, start + n)`` of the replay."""
        idx = np.arange(start, start + n, dtype=np.int64)
        lap, off = np.divmod(idx, self.size)
        base = self.packets[off, 0].astype(np.int64)
        return (base + lap * self.id_span) & ((1 << self.id_bits) - 1)


def _sizes(rng, modes, n: int) -> np.ndarray:
    """Per-packet sizes from ``[[weight, mean, sd], ...]`` normal modes."""
    w = np.asarray([m[0] for m in modes], np.float64)
    pick = rng.choice(len(modes), size=n, p=w / w.sum())
    mean = np.asarray([m[1] for m in modes])[pick]
    sd = np.asarray([m[2] for m in modes])[pick]
    return np.clip(rng.normal(mean, sd), MIN_LEN, MAX_LEN)


def _gaps(rng, gap, n: int) -> np.ndarray:
    """Lognormal inter-packet gaps: ``gap = [median_s, sigma]``."""
    return np.clip(rng.lognormal(math.log(gap[0]), gap[1], n), 1e-5, 600.0)


def _attack_flow_share(kinds, attack) -> float:
    """Flow share of attack flows that gives them ``pkt_share`` packets."""
    if not attack or attack["pkt_share"] <= 0:
        return 0.0
    share = np.asarray([k["flow_share"] for k in kinds], np.float64)
    share /= share.sum()
    mean_b = float(sum(s * (k["pkts"][0] + k["pkts"][1] - 1) / 2
                       for s, k in zip(share, kinds)))
    mean_a = (attack["pkts"][0] + attack["pkts"][1] - 1) / 2
    p = attack["pkt_share"]
    return p * mean_b / ((1 - p) * mean_a + p * mean_b)


def _rank_in_group(group: np.ndarray, n_groups: int):
    """Arrival rank of each element within its group, and group sizes."""
    counts = np.bincount(group, minlength=n_groups)
    order = np.argsort(group, kind="stable")
    starts = np.cumsum(counts) - counts
    rank = np.empty(len(group), np.int64)
    rank[order] = np.arange(len(group)) - starts[group[order]]
    return rank, counts


def _background(rng, mix, n: int):
    """Lane traffic -> (flow index, kind index, first-packet flag, size,
    gap, port) per packet; flow indices are dense from 0."""
    kinds = list(mix["kinds"])
    attack = mix.get("attack")
    p_att = _attack_flow_share(kinds, attack)
    all_kinds = kinds + ([attack] if p_att > 0 else [])
    share = np.asarray([k["flow_share"] for k in kinds], np.float64)
    probs = np.concatenate([share / share.sum() * (1 - p_att),
                            [p_att] if p_att > 0 else []])

    L = int(mix["flows_active"])
    lane = rng.integers(0, L, size=n)
    rank, counts = _rank_in_group(lane, L)
    min_len = min(k["pkts"][0] for k in all_kinds)
    K = 2 + int(math.ceil(int(counts.max(initial=0)) / max(1, min_len)))

    kind = rng.choice(len(all_kinds), size=(L, K), p=probs)
    length = np.empty((L, K), np.int64)
    port = np.empty((L, K), np.float32)
    for i, k in enumerate(all_kinds):
        m = kind == i
        length[m] = rng.integers(k["pkts"][0], k["pkts"][1], size=int(m.sum()))
        port[m] = rng.choice(np.asarray(k["ports"], np.float32),
                             size=int(m.sum()))
    # the flow a lane is in when the lap starts has run for some packets
    length[:, 0] = rng.integers(1, length[:, 0] + 1)
    ends = np.cumsum(length, axis=1)

    k_of = np.zeros(n, np.int64)
    for j in range(K - 1):
        k_of += rank >= ends[lane, j]
    start_of = np.where(k_of > 0, ends[lane, np.maximum(k_of - 1, 0)], 0)
    first = rank == start_of

    cell = lane * K + k_of
    used = np.zeros(L * K, bool)
    used[cell] = True
    dense = np.cumsum(used) - 1
    flow = dense[cell]
    pkt_kind = kind.reshape(-1)[cell]

    size = np.empty(n, np.float64)
    gap = np.empty(n, np.float64)
    for i, k in enumerate(all_kinds):
        m = pkt_kind == i
        size[m] = _sizes(rng, k["size_modes"], int(m.sum()))
        gap[m] = _gaps(rng, k["gap_s"], int(m.sum()))
    return flow, int(used.sum()), first, size, gap, port.reshape(-1)[cell]


def _flood(rng, flood, n: int):
    """Flood traffic -> (flow index, first flag, size, gap, port)."""
    F, per = int(flood["flows"]), int(flood["flow_pkts"])
    lane = rng.integers(0, F, size=n)
    rank, _ = _rank_in_group(lane, F)
    pos = rank + rng.integers(0, per, size=F)[lane]
    k = pos // per
    first = (pos % per == 0) | (rank == 0)
    n_k = int(k.max(initial=0)) + 1
    flow = lane * n_k + k
    used = np.zeros(F * n_k, bool)
    used[flow] = True
    flow = (np.cumsum(used) - 1)[flow]
    size = _sizes(rng, flood["size_modes"], n)
    gap = _gaps(rng, flood["gap_s"], n)
    port = rng.choice(np.asarray(flood["ports"], np.float32), size=n)
    return flow, int(used.sum()), first, size, gap, port


def lap_size(mix) -> int:
    return int(mix["lap_per_flow"]) * int(mix["flows_active"])


def make_lap(mix: dict, seed: int) -> Lap:
    """Build one lap of the mix from ``seed`` (any non-negative int)."""
    rng = np.random.default_rng(int(seed))
    n = lap_size(mix)
    flood = mix.get("flood")
    if flood:
        is_flood = rng.random(n) < float(flood["pkt_share"])
    else:
        is_flood = np.zeros(n, bool)
    n_fl = int(is_flood.sum())

    flow = np.empty(n, np.int64)
    first = np.empty(n, bool)
    size = np.empty(n, np.float64)
    gap = np.empty(n, np.float64)
    port = np.empty(n, np.float32)
    bg = ~is_flood
    f_b, n_b, first[bg], size[bg], gap[bg], port[bg] = _background(
        rng, mix, n - n_fl)
    flow[bg] = f_b
    n_ids = n_b
    if n_fl:
        f_f, n_f, first[is_flood], size[is_flood], gap[is_flood], \
            port[is_flood] = _flood(rng, flood, n_fl)
        flow[is_flood] = n_b + f_f
        n_ids += n_f

    id_bits = int(mix["id_bits"])
    span = 1 << max(1, math.ceil(math.log2(n_ids)))
    if span > (1 << id_bits):
        raise ValueError(f"{n_ids} flows per lap exceed 2**{id_bits} ids")
    ids = rng.permutation(span)[:n_ids]
    packets = np.empty((n, 4), np.float32)
    packets[:, 0] = ids[flow]
    packets[:, 1] = size
    packets[:, 2] = np.where(first, 0.0, gap)
    packets[:, 3] = port
    return Lap(packets, span, id_bits, is_flood)

