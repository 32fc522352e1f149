"""Seconds from process start to the first request being due: the chip
found, weights made, programs loaded or compiled, warm-up batches served."""


def read(ctx):
    return ctx.setup_s
