"""Fused kernel (``kernels/fused_flow``): device time of the fused
``pallas_call`` per step, the mean over the devices.  The launch carries
no name of its own yet; its op is named after the jitted wrapper,
``fused_flow_serve_padded.<n>`` (``KERNEL``)."""

import re

KERNEL = re.compile(r"^fused_flow_serve_padded")


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None

    def per_launch(d):
        n = sum(c for op, c in d.op_count.items() if KERNEL.match(op))
        ns = sum(v for op, v in d.op_ns.items() if KERNEL.match(op))
        return ns / n * 1e-3 if n else None

    return red.mean(per_launch)
