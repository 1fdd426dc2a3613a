"""XLA prelude and epilogue (``kernels/flow_update/ops.py``: slot sort,
row gathers, tail scatters): the op time of the step outside the fused
kernel, per step, the mean over the devices.  The step is the module in
which the kernel's op (``KERNEL``) runs."""

import re

KERNEL = re.compile(r"^fused_flow_serve_padded")


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None

    def per_step(d):
        steps = {m for m, ops in d.module_op_ns.items()
                 if m is not None and any(KERNEL.match(o) for o in ops)}
        n = sum(d.module_count[m] for m in steps)
        ns = sum(v for m in steps for op, v in d.module_op_ns[m].items()
                 if not KERNEL.match(op))
        return ns / n * 1e-3 if n else None

    return red.mean(per_step)
