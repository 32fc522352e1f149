"""Device milliseconds per fuser decode step: program runs launched inside
the stream batcher's ``pump``, over the traced stretch."""

from harness import trace


def read(ctx):
    runs = trace.per_span_program_ms(ctx.trace, "bench.decode")
    return None if runs is None else runs[0] / runs[1]
