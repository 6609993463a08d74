"""Bytes the fabric epoch copies to the host per key of the relation: the
``bytes`` of the ``epoch:fetch`` spans over the ``n`` of the ``pipeline``
spans (a count)."""

import spans


def read(ctx):
    fetches = spans.named(ctx, "epoch:fetch")
    jobs = spans.named(ctx, "pipeline")
    if not fetches or not jobs:
        return None
    keys = sum(spans.arg(ev, "n") for ev in jobs)
    return sum(spans.arg(ev, "bytes") for ev in fetches) / keys
