"""Share of its HBM roofline that the Pallas block sort
(``repro.kernels.ops.sort_rows_padded``) reaches, in percent: reading and
writing its padded (rows, segment_length) int32 matrix once at the chip's
peak bandwidth, over the kernel's device time."""


def read(ctx):
    return ctx.hbm_share(ctx.kernel_calls("jit(_sort_rows_padded)"))
