"""Pipeline step (``flowstate/pipeline.py`` ``_step``): device time of the
jitted step program per step, from the trace's module line, the mean over
the devices the cell uses.  The step carries no stable name of its own
yet (``jit_fused_fn(<hash>)`` on one chip); it is the module in which the
fused kernel (``KERNEL``, the ``pallas_call``'s op) runs."""

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
        return sum(d.module_ns[m] for m in steps) / n * 1e-3 if n else None

    return red.mean(per_step)
