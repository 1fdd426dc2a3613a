"""1 - (union of device op intervals) / (traced stretch), in %, the mean
over the devices the cell uses."""


def read(ctx):
    red = ctx.reduced
    if red is None or ctx.stretch_s <= 0 or not red.devices:
        return None
    busy = red.mean(lambda d: d.busy_ns) * 1e-9
    return (1.0 - busy / ctx.stretch_s) * 100.0
