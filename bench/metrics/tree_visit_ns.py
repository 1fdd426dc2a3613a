"""Fused kernel forest walk (``kernels/fused_flow``): the fused kernel's
device time per launch over the node visits of that launch, in ns, the
mean over the devices.  Node visits per launch are the window's real
rows per launch (per device) times the node visits a packet makes: the
``fused_suffix`` counter ``node_visits_per_packet`` (trees x levels of the
complete trees) that the engine's ``stats()`` reports.  The window keeps
only the engine's packet, batch and dispatch counters, so the counter is
read where ``stats()`` takes it from, the configuration's pipeline
(``StatefulPipeline.fused_suffix``), built again; a program without the
counter reads None.  The kernel's time includes the register prefix's
few µs per launch (3.8 µs in ``flow-ddos-mlp``) besides the walk."""

import re

KERNEL = re.compile(r"^fused_flow_serve_padded")


def node_visits_per_packet(config: dict):
    from bench import pipelines

    counters = getattr(pipelines.Built(config).pipeline, "fused_suffix",
                       None) or {}
    return counters.get("node_visits_per_packet") or None


def read(ctx):
    red = ctx.reduced
    w = ctx.served.window
    batches = w.stats_close["batches"] - w.stats_open["batches"]
    if red is None or batches <= 0:
        return None
    visits = node_visits_per_packet(ctx.config)
    if visits is None:
        return None
    rows = (w.stats_close["packets"] - w.stats_open["packets"]) / batches
    per_launch = rows / ctx.n_devices * visits

    def per_visit(d):
        n = sum(c for op, c in d.op_count.items() if KERNEL.match(op))
        ns = sum(v for op, v in d.op_ns.items() if KERNEL.match(op))
        return ns / n / per_launch if n and per_launch else None

    return red.mean(per_visit)
