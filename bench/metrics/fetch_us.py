"""Verdict fetch (``serve.fetch``): the blocking copy of a batch's
verdicts to the host, the unshard or slice and the mitigated count; self
time per batch in the traced stretch (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.per_batch_us(ctx, "serve.fetch")
