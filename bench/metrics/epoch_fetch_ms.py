"""Time of the fabric epoch's result on its way to the host per job, in
milliseconds: the duration of the ``epoch:fetch`` span, which starts once
the epoch's program has finished on the device and holds the result's
copy to the host and its assembly into the egress wire batch."""

import spans


def read(ctx):
    fetches = spans.named(ctx, "epoch:fetch")
    if not fetches:
        return None
    return sum(ev.dur for ev in fetches) / 1e3 / ctx.jobs
