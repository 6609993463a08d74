"""Device time of the wide records' server merge per job, in milliseconds:
the runs of the multi-word tournament programs
(``repro.kernels.ops.merge_tournament`` on stacked sort words, Pallas or
XLA)."""


def read(ctx):
    runs = ctx.modules("jit__merge_tournament_stacked")
    if not runs:
        return None
    return sum(ev.dur for ev in runs) / 1e3 / ctx.jobs
