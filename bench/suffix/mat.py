"""Match-action-table (range table) classifier suffix.

``Quantize -> LUTGather -> Reduce(argmax) -> LabelMap``, with the tables
of ``benchmarks/flow_throughput.build_mat_pipeline``: from ``table_seed``,
per feature 7 sorted random edges in [0, 1) (feature 0, the packet count,
gets the edges 1..7) and 8 x 4 random partial scores; ids map to classes
through ``label_map``.  The reference sums the partial scores in float32
in feature order, as the stated precision and the stage order give; a
served verdict must be one the reference reaches with each divided input
exact or one float32 step off (``possible``).
"""

from __future__ import annotations

import numpy as np


def params(cfg: dict) -> dict:
    rng = np.random.default_rng(int(cfg["table_seed"]))
    n_in, n_edges = int(cfg["n_in"]), int(cfg["n_edges"])
    edges = np.sort(rng.random((n_in, n_edges)).astype(np.float32), axis=1)
    edges[0] = np.arange(1.0, n_edges + 1.0, dtype=np.float32)
    tables = rng.random((n_in, n_edges + 1, int(cfg["n_ids"]))).astype(
        np.float32)
    return {"edges": edges, "tables": tables,
            "label_map": np.asarray(cfg["label_map"], np.int64)}


def stages(p: dict) -> list:
    from repro.core import stageir

    return [stageir.Quantize(p["edges"]), stageir.LUTGather(p["tables"]),
            stageir.Reduce("argmax"),
            stageir.LabelMap(p["label_map"].astype(np.int32))]


def ops(cfg: dict) -> int:
    """Necessary operations per packet: per feature one compare per edge
    and one add per id score."""
    return int(cfg["n_in"]) * (int(cfg["n_edges"]) + int(cfg["n_ids"]))


def _high(t: np.ndarray) -> np.ndarray:
    """A float32 table value as a one-hot matmul at ``Precision.HIGH``
    delivers it: its two leading bfloat16 parts."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    hi = t.astype(bf).astype(np.float32)
    return hi + (t - hi).astype(bf).astype(np.float32)


def scores(z: np.ndarray, p: dict, control: bool = False) -> np.ndarray:
    """Readout rows -> per-id scores, float32 summed in feature order."""
    z = z.astype(np.float32)
    tables = _high(p["tables"]) if control else p["tables"]
    s = np.zeros((len(z), tables.shape[2]), np.float32)
    for f in range(tables.shape[0]):
        b = np.searchsorted(p["edges"][f], z[:, f], side="left")
        s = s + tables[f][b]
    return s


def verdicts(s: np.ndarray, p: dict) -> np.ndarray:
    return p["label_map"][np.argmax(s, axis=1)]


def possible(z, z_lo, z_hi, s, p: dict, limits: dict) -> np.ndarray:
    """[n, classes] bool: the classes a packet may get when each divided
    input may be one float32 step off (``z_lo``/``z_hi``).  Only packets
    with an edge within that step of an input have more than one."""
    lm = p["label_map"]
    out = np.zeros((len(z), int(lm.max()) + 1), bool)
    out[np.arange(len(z)), verdicts(s, p)] = True
    edges, tables = p["edges"], p["tables"]
    b_lo = np.stack([np.searchsorted(edges[f], z_lo[:, f].astype(np.float32))
                     for f in range(len(edges))], 1)
    b_hi = np.stack([np.searchsorted(edges[f], z_hi[:, f].astype(np.float32))
                     for f in range(len(edges))], 1)
    for i in np.flatnonzero(np.any(b_lo != b_hi, axis=1)):
        amb = np.flatnonzero(b_lo[i] != b_hi[i])[:8]
        for bits in range(1 << len(amb)):
            b = b_lo[i].copy()
            for j, f in enumerate(amb):
                if bits >> j & 1:
                    b[f] = b_hi[i, f]
            sc = np.zeros(tables.shape[2], np.float32)
            for f in range(len(edges)):
                sc = sc + tables[f][b[f]]
            out[i, lm[np.argmax(sc)]] = True
    return out


VERDICT_NUMBER = "verdict_mismatch"
