"""Host time of the records' payload gather per job, in milliseconds: the
duration of the ``pipeline:payload`` span, the one gather of the payload
rows into the sorted order."""

import spans


def read(ctx):
    gathers = spans.named(ctx, "pipeline:payload")
    if not gathers:
        return None
    return sum(ev.dur for ev in gathers) / 1e3 / ctx.jobs
