"""Engine host path: ``stats()["dispatch_s"]`` (host clock around staging
and launching a batch) over the batches dispatched in the window."""


def read(ctx):
    w = ctx.served.window
    n = w.stats_close["batches"] - w.stats_open["batches"]
    if n <= 0:
        return None
    return (w.stats_close["dispatch_s"] - w.stats_open["dispatch_s"]) / n * 1e6
