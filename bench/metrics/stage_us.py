"""Engine staging (``serve.stage``): the swap-boundary check, the queue
take, the staging-ring copy and the valid mask, and on four chips the
per-shard scatter into the staging buffer; self time per batch in the
traced stretch (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.per_batch_us(ctx, "serve.stage")
