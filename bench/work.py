"""The necessary work of serving one packet, from a configuration's shapes.

What a packet needs, whatever implements it:

* bytes: its packet row in (``FEATURE_COLS`` float32 words), its slot's
  register row read and written (``2 * width`` words), the stored key read
  and written (2 words), its verdict out (1 word); with mitigation also
  the action row read and written (2 * 2 words) and its key (2 words);
* operations: the register update (one add per counter, three per EWMA,
  one per histogram), the readout's divisions (one per histogram bin),
  and the suffix's: ``2 * n_in * n_out`` per MLP layer plus its biases
  and ReLUs, or per MAT feature one compare per edge and one add per id
  score; with mitigation four more.

A kernel's roofline share and the step's utilisation divide the least
time of this work at the chip's peaks by a measured time.
"""

from __future__ import annotations

import json
from pathlib import Path

WORD = 4
FEATURE_COLS = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def register_width(prefix: dict) -> int:
    return 2 + 2 + int(prefix["pl_bins"]) + int(prefix["ipt_bins"])


def bytes_per_packet(config: dict) -> int:
    w = register_width(config["prefix"])
    words = FEATURE_COLS + 2 * w + 2 + 1
    if config.get("mitigation"):
        words += 2 * 2 + 2
    return words * WORD


def suffix_ops(suffix: dict) -> int:
    if suffix["kind"] == "mlp":
        widths = [int(x) for x in suffix["widths"]]
        ops = 0
        for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
            ops += 2 * a * b + b + (b if i < len(widths) - 2 else 0)
        return ops
    if suffix["kind"] == "mat":
        return int(suffix["n_in"]) * (int(suffix["n_edges"])
                                      + int(suffix["n_ids"]))
    raise KeyError(f"no operation count for suffix kind {suffix['kind']!r}")


def ops_per_packet(config: dict) -> int:
    pre = config["prefix"]
    hist = int(pre["pl_bins"]) + int(pre["ipt_bins"])
    update = 2 + 3 * 2 + 2
    ops = update + hist + suffix_ops(config["suffix"])
    if config.get("mitigation"):
        ops += 4
    return ops


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def least_seconds(config: dict, packets: int, device_kind: str) -> tuple:
    """(least seconds for ``packets`` packets at the peaks, the bound:
    ``"bytes"`` or ``"flops"``)."""
    pk = peaks(device_kind)
    t_b = packets * bytes_per_packet(config) / pk["hbm_bytes_per_s"]
    t_f = packets * ops_per_packet(config) / pk["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
