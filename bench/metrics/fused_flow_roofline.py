"""Fused kernel's roofline share, in %: the least time that the packets
it served in the traced stretch need at the chip's peaks
(``bench/work.py``; bytes bound both configurations), over the kernel's
measured time, the mean over the devices.  Packets per launch are the
window's real rows per batch over the devices."""

import re

from bench import work

KERNEL = re.compile(r"^fused_flow_serve_padded")


def read(ctx):
    red = ctx.reduced
    w = ctx.served.window
    batches = w.stats_close["batches"] - w.stats_open["batches"]
    if red is None or batches <= 0:
        return None
    rows = (w.stats_close["packets"] - w.stats_open["packets"]) / batches
    per_launch = rows / ctx.n_devices

    def share(d):
        n = sum(c for op, c in d.op_count.items() if KERNEL.match(op))
        ns = sum(v for op, v in d.op_ns.items() if KERNEL.match(op))
        if not n or not ns:
            return None
        least, _bound = work.least_seconds(ctx.config, n * per_launch,
                                           ctx.device_kind)
        return least / (ns * 1e-9) * 100.0

    return red.mean(share)
