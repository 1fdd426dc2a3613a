"""Whole step's share of the chips' peak, in %: the least time that the
necessary work of every packet whose verdict reached the host in the
traced stretch needs at the peaks of the cell's chips (``bench/work.py``),
over the stretch's wall time."""

from bench import work


def read(ctx):
    if ctx.reduced is None or ctx.stretch_s <= 0 or ctx.stretch_packets <= 0:
        return None
    least, _bound = work.least_seconds(ctx.config, ctx.stretch_packets,
                                       ctx.device_kind)
    return least / ctx.n_devices / ctx.stretch_s * 100.0
