"""Device time of the server merge's tournaments
(``repro.kernels.ops.merge_tournament``, Pallas or XLA) per job, in
milliseconds."""


def read(ctx):
    runs = ctx.modules("jit__merge_tournament")
    if not runs:
        return None
    return sum(ev.dur for ev in runs) / 1e3 / ctx.jobs
