"""Host time of the pipeline's result assembly per job, in milliseconds:
the self time of the ``pipeline:result`` span (the delivered wire's
per-segment streams, ``repro.net.wire.segment_streams_batch``, and the
``PipelineResult``)."""

import spans


def read(ctx):
    return spans.self_ms_per_job(ctx, "pipeline:result")
