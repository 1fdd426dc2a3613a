"""The necessary work of serving one packet, from a configuration's shapes.

What a packet needs, whatever implements it:

* bytes: its packet row in (``FEATURE_COLS`` float32 words), its slot's
  register row read and written (``2 * width`` words), the stored key read
  and written (2 words), its verdict out (1 word); with mitigation also
  the action row read and written (2 * 2 words) and its key (2 words).
  A suffix's weights and tables are constants of the launch and add none;
* operations: the register update (one add per counter, three per EWMA,
  one per histogram), the readout's divisions (one per histogram bin),
  the suffix's, which its kind's module counts (``ops`` in
  ``bench/suffix/<kind>.py``), and with mitigation four more.

A kernel's roofline share and the step's utilisation divide the least
time of this work at the chip's peaks by a measured time.
"""

from __future__ import annotations

import json
from pathlib import Path

from bench import spec

WORD = 4
FEATURE_COLS = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"
SUFFIX_DIR = spec.BENCH / "suffix"


def register_width(prefix: dict) -> int:
    return 2 + 2 + int(prefix["pl_bins"]) + int(prefix["ipt_bins"])


def bytes_per_packet(config: dict) -> int:
    w = register_width(config["prefix"])
    words = FEATURE_COLS + 2 * w + 2 + 1
    if config.get("mitigation"):
        words += 2 * 2 + 2
    return words * WORD


def suffix_ops(suffix: dict, suffix_dir: Path = SUFFIX_DIR) -> int:
    """Operations per packet of the configuration's ``suffix``, from its
    kind's module; a kind whose module has no ``ops`` is an error."""
    count = getattr(spec.suffix_kind(suffix["kind"], suffix_dir), "ops", None)
    if count is None:
        raise KeyError(f"no operation count for suffix kind "
                       f"{suffix['kind']!r}: its module has no ops()")
    return int(count(suffix))


def ops_per_packet(config: dict, suffix_dir: Path = SUFFIX_DIR) -> int:
    pre = config["prefix"]
    hist = int(pre["pl_bins"]) + int(pre["ipt_bins"])
    update = 2 + 3 * 2 + 2
    ops = update + hist + suffix_ops(config["suffix"], suffix_dir)
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
