"""Share of its HBM roofline that the Pallas tournament merge
(``repro.kernels.ops.merge_tournament``) reaches, in percent: reading and
writing its (rows, 128) matrix once, at the dtype the kernel sees (int32),
at the chip's peak bandwidth, over the kernel's device time."""


def read(ctx):
    return ctx.hbm_share(ctx.kernel_calls("jit(_merge_tournament)"))
