"""The telemetry plane's own per-batch cost (``serve.record``): the
counters and histograms of a dispatched batch and the sampled slot
segmentation (on four chips with the shard-id fold before it); self time
per batch in the traced stretch (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.per_batch_us(ctx, "serve.record")
