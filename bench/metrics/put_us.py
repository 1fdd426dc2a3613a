"""Host-to-device copy of a batch (``serve.put``): the staged rows and
the valid mask to the device (on four chips, one block per chip), JAX's
``DevicePutWithSharding`` and ``shard_args`` beneath included; self time
per batch in the traced stretch (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.per_batch_us(ctx, "serve.put")
