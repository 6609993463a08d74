"""Host time of the server merge per job, in milliseconds: the time inside
the ``server<s>:ingest`` and ``server<s>:finish`` spans in which no
operation runs on the device (run detection, the tournaments' matrix
layout and copies, the host merge of their winners)."""

import spans


def read(ctx):
    servers = spans.named(ctx, r"server\d+:(ingest|finish)")
    if not servers:
        return None
    return spans.idle_us(ctx, [(ev.start, ev.end) for ev in servers]) / (
        1e3 * ctx.jobs
    )
