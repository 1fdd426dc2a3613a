"""Random-forest classifier suffix: complete trees with a majority vote.

Breiman's random forest (Machine Learning 45, 2001) as in-network
detectors serve it: ``n_trees`` trees, each complete to ``depth`` levels;
a node sends a packet left when ``x[feat] <= thr`` (as scikit-learn
splits); each tree votes its leaf's class and the verdict is the class
with the most votes, ties to the lowest class id.

``params`` draws the trees from the configuration's ``tree_seed``, not
from training: each inner node's feature uniformly among the features
whose stated ``thresholds`` (per-feature quantiles of real readouts) are
not all equal, its threshold uniformly among that feature's distinct
quantiles, and each leaf's class uniformly.  Nodes are numbered as a heap
from the root, 0: node ``j`` has children ``2j + 1`` and ``2j + 2``.  The
reference walks the trees in float32 (the readout as it is); a served
verdict must be one the reference reaches with each divided input exact
or one float32 step off (``possible``).
"""

from __future__ import annotations

import itertools

import numpy as np

MAX_EITHER_WAY = 8


def params(cfg: dict) -> dict:
    rng = np.random.default_rng(int(cfg["tree_seed"]))
    T, D = int(cfg["n_trees"]), int(cfg["depth"])
    pools = [np.unique(np.asarray(q, np.float32)) for q in cfg["thresholds"]]
    usable = np.asarray([f for f, q in enumerate(pools) if len(q) > 1])
    n_inner = (1 << D) - 1
    feat = usable[rng.integers(0, len(usable), (T, n_inner))]
    sizes = np.asarray([len(q) for q in pools])
    pick = (rng.random((T, n_inner)) * sizes[feat]).astype(np.int64)
    thr = np.asarray([pools[f][i] for f, i in zip(feat.ravel(), pick.ravel())],
                     np.float32).reshape(T, n_inner)
    leaf = rng.integers(0, int(cfg["n_classes"]), (T, 1 << D))
    return {"feat": feat.astype(np.int64), "thr": thr, "leaf": leaf,
            "depth": D, "n_classes": int(cfg["n_classes"])}


def stages(p: dict) -> list:
    """The program's suffix: one ``TreeTraverse`` over all the trees."""
    from repro.core import stageir

    T, n_inner = p["feat"].shape
    n = 2 * n_inner + 1
    nodes = np.arange(n)
    inner = nodes < n_inner
    left = np.where(inner, 2 * nodes + 1, nodes)
    right = np.where(inner, 2 * nodes + 2, nodes)
    feat = np.zeros((T, n), np.int32)
    thr = np.zeros((T, n), np.float32)
    leaf_class = np.zeros((T, n), np.int32)
    feat[:, :n_inner] = p["feat"]
    thr[:, :n_inner] = p["thr"]
    leaf_class[:, n_inner:] = p["leaf"]
    return [stageir.TreeTraverse(
        feat, thr, np.tile(left, (T, 1)), np.tile(right, (T, 1)),
        leaf_class, np.tile(~inner, (T, 1)), p["depth"])]


def ops(cfg: dict) -> int:
    """Necessary operations per packet: per tree and level one compare and
    one child step, one vote add per tree, one compare per class for the
    argmax.  32 trees of depth 10 on two classes: 32 * 10 * 2 + 32 + 2 =
    674."""
    T, D = int(cfg["n_trees"]), int(cfg["depth"])
    return T * D * 2 + T + int(cfg["n_classes"])


def _leaves(z: np.ndarray, p: dict) -> np.ndarray:
    """[n, T] leaf each packet reaches in each tree."""
    z = z.astype(np.float32)
    T, n_inner = p["feat"].shape
    rows = np.arange(len(z))
    out = np.empty((len(z), T), np.int64)
    for t in range(T):
        j = np.zeros(len(z), np.int64)
        for _ in range(p["depth"]):
            left = z[rows, p["feat"][t, j]] <= p["thr"][t, j]
            j = 2 * j + np.where(left, 1, 2)
        out[:, t] = j - n_inner
    return out


def scores(z: np.ndarray, p: dict, control: bool = False) -> np.ndarray:
    """Readout rows -> [n, classes] vote counts over the trees.  The
    control differs in its readout (bfloat16 registers), not here."""
    cls = p["leaf"][np.arange(p["leaf"].shape[0]), _leaves(z, p)]
    votes = np.zeros((len(z), p["n_classes"]), np.int64)
    for c in range(p["n_classes"]):
        votes[:, c] = np.sum(cls == c, axis=1)
    return votes


def verdicts(s: np.ndarray, p: dict) -> np.ndarray:
    return np.argmax(s, axis=1).astype(np.int64)


def _steps(p: dict) -> list:
    """Per feature, the sorted distinct thresholds of the forest's nodes."""
    return [np.unique(p["thr"][p["feat"] == f])
            for f in range(int(p["feat"].max()) + 1)]


def possible(z, z_lo, z_hi, s, p: dict, limits: dict) -> np.ndarray:
    """[n, classes] bool: the classes a packet may get when each divided
    input may be one float32 step off (``z_lo``/``z_hi``; a zero quotient
    is exact, 0 / n being 0 on any division).  An input is either-way
    when a threshold of its feature lies in ``[z_lo, z_hi)``; for a
    packet with such inputs, the either-way features whose thresholds
    sit on its own paths first, up to ``MAX_EITHER_WAY``, take each
    value in ``{z_lo, z, z_hi}`` that decides differently, in every
    combination, and every class the vote reaches is possible.  Only a
    packet whose paths pass a threshold within a step of its input gets
    more than one class."""
    z = z.astype(np.float32)
    zero = z == 0
    lo = np.where(zero, z, z_lo).astype(np.float32)
    hi = np.where(zero, z, z_hi).astype(np.float32)
    out = np.zeros((len(z), p["n_classes"]), bool)
    out[np.arange(len(z)), verdicts(s, p)] = True
    steps = _steps(p)
    F = len(steps)
    side = [np.stack([np.searchsorted(steps[f], v[:, f], side="left")
                      for f in range(F)], 1) for v in (lo, z, hi)]
    either = side[0] != side[2]
    rows = np.flatnonzero(either.any(axis=1))
    if not len(rows):
        return out
    on_path = _path_features(z[rows], p, F)
    combos, owner = [], []
    for k, i in enumerate(rows):
        fs = np.flatnonzero(either[i])
        fs = fs[np.argsort(~on_path[k, fs], kind="stable")][:MAX_EITHER_WAY]
        choices = []
        for f in fs:
            vals, seen = [], set()
            for v, sd in zip((lo[i, f], z[i, f], hi[i, f]),
                             (side[0][i, f], side[1][i, f], side[2][i, f])):
                if sd not in seen:
                    seen.add(sd)
                    vals.append(v)
            choices.append(vals)
        for pick in itertools.product(*choices):
            row = z[i].copy()
            row[fs] = pick
            combos.append(row)
            owner.append(i)
    combos = np.asarray(combos, np.float32)
    owner = np.asarray(owner)
    out[owner, verdicts(scores(combos, p), p)] = True
    return out


def _path_features(z: np.ndarray, p: dict, n_features: int) -> np.ndarray:
    """[n, F] bool: features split on at some node of the packet's paths."""
    T, n_inner = p["feat"].shape
    rows = np.arange(len(z))
    seen = np.zeros((len(z), n_features), bool)
    for t in range(T):
        j = np.zeros(len(z), np.int64)
        for _ in range(p["depth"]):
            f = p["feat"][t, j]
            seen[rows, f] = True
            j = 2 * j + np.where(z[rows, f] <= p["thr"][t, j], 1, 2)
    return seen


VERDICT_NUMBER = "verdict_mismatch"
