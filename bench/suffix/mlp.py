"""ReLU MLP classifier suffix with the input standardisation folded in.

``params`` makes the weights from the configuration's own ``weight_seed``
(He-scaled normal weights, small normal biases) and folds the stated
feature moments into the first layer, ``x @ (W / sd) + (b - (mu / sd) @ W)``,
in float32, as ``traffic.fold_input_standardization`` does, and adds
``logit_shift`` to the output bias.  The program
serves them as ``FusedMLP -> Reduce(argmax)``; the reference computes the
logits in float64 from the float32 readout.
"""

from __future__ import annotations

import numpy as np


def params(cfg: dict) -> dict:
    widths = [int(w) for w in cfg["widths"]]
    rng = np.random.default_rng(int(cfg["weight_seed"]))
    ws, bs = [], []
    for n_in, n_out in zip(widths[:-1], widths[1:]):
        ws.append((rng.standard_normal((n_in, n_out))
                   * np.sqrt(2.0 / n_in)).astype(np.float32))
        bs.append((rng.standard_normal(n_out) * 0.1).astype(np.float32))
    mu = np.asarray(cfg["feature_mean"], np.float32)
    sd = np.asarray(cfg["feature_std"], np.float32)
    w0, b0 = ws[0], bs[0]
    ws[0] = (w0 / sd[:, None]).astype(np.float32)
    bs[0] = (b0 - (mu / sd) @ w0).astype(np.float32)
    bs[-1] = (bs[-1] + np.asarray(cfg["logit_shift"], np.float32)).astype(
        np.float32)
    return {"weights": ws, "biases": bs}


def stages(p: dict) -> list:
    """The program's suffix stages (the only part that imports it)."""
    from repro.core import stageir

    return [stageir.FusedMLP(list(p["weights"]), list(p["biases"])),
            stageir.Reduce("argmax")]


def ops(cfg: dict) -> int:
    """Necessary operations per packet: ``2 * n_in * n_out`` per layer,
    its biases, and a ReLU per unit on every layer but the last."""
    widths = [int(x) for x in cfg["widths"]]
    n = 0
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        n += 2 * a * b + b + (b if i < len(widths) - 2 else 0)
    return n


def _dot_high(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """float32 matmul in three bfloat16 passes (``Precision.HIGH``):
    hi*hi + hi*lo + lo*hi, accumulated in float32."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16

    def split(a):
        hi = a.astype(bf).astype(np.float32)
        return hi, (a - hi).astype(bf).astype(np.float32)

    xh, xl = split(x.astype(np.float32))
    wh, wl = split(w.astype(np.float32))
    return (xh @ wh + xh @ wl + xl @ wh).astype(np.float32)


def scores(z: np.ndarray, p: dict, control: bool = False) -> np.ndarray:
    """Readout rows -> logits: float64, or the control's three passes."""
    n = len(p["weights"])
    if control:
        h = z.astype(np.float32)
        for i, (w, b) in enumerate(zip(p["weights"], p["biases"])):
            h = _dot_high(h, w) + b
            if i < n - 1:
                h = np.maximum(h, 0.0)
        return h.astype(np.float64)
    h = z.astype(np.float64)
    for i, (w, b) in enumerate(zip(p["weights"], p["biases"])):
        h = h @ w.astype(np.float64) + b.astype(np.float64)
        if i < n - 1:
            h = np.maximum(h, 0.0)
    return h


def verdicts(s: np.ndarray, p: dict) -> np.ndarray:
    return np.argmax(s, axis=1).astype(np.int64)


def gap(s: np.ndarray, v: np.ndarray, p: dict) -> np.ndarray:
    """How far below the best logit the served class's logit lies."""
    return s.max(1) - s[np.arange(len(v)), v]


def possible(z, z_lo, z_hi, s, p: dict, limits: dict) -> np.ndarray:
    """[n, classes] bool: classes within the gap limit of the best."""
    return s.max(1, keepdims=True) - s <= limits["verdict_gap"]


# what the check compares for this suffix: the served class's logit gap
VERDICT_NUMBER = "verdict_gap"
