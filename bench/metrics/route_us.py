"""Flow-key routing of the sharded engine (``serve.route``): flow keys,
shard ids, the arrival-order prefix that fits every shard and the
overflow pushed back; self time per batch in the traced stretch
(``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.per_batch_us(ctx, "serve.route")
