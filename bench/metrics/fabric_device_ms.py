"""Device time of the compiled fabric epoch (``repro.net.device_epoch``'s
``epoch_fn``) per job, in milliseconds."""


def read(ctx):
    runs = ctx.modules("jit_epoch_fn")
    if not runs:
        return None
    return sum(ev.dur for ev in runs) / 1e3 / ctx.jobs
