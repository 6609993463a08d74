"""Host time of the pipeline's ingress per job, in milliseconds: the self
time of the ``pipeline:ingress`` span (splitting the relation into flows
and interleaving them onto the ingress link, ``repro.net.flow``)."""

import spans


def read(ctx):
    return spans.self_ms_per_job(ctx, "pipeline:ingress")
