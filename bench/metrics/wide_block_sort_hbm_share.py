"""Share of its HBM roofline that the multi-word Pallas block sort
(``repro.kernels.ops.sort_rows_padded`` on stacked sort words, the wide-key
device epoch's) reaches, in percent: reading and writing its stacked
(words, rows, segment_length) int32 array once at the chip's peak
bandwidth, over the kernel's device time.  The kernel returns every word
in that one stacked result, so ``tracefile.op_bytes`` counts them all."""


def read(ctx):
    return ctx.hbm_share(ctx.kernel_calls("jit(_sort_rows_padded_stacked)"))
