"""Device milliseconds per batch of the fuser programs launched inside the
stream batcher's ``submit`` (encoder prefill and the join into decode
slots), over the traced stretch."""

from harness import trace


def read(ctx):
    runs = trace.per_span_program_ms(ctx.trace, "bench.prefill")
    batches = ctx.trace.spans.named("bench.prefill", *ctx.trace.window_ns)
    if runs is None or not batches:
        return None
    return runs[0] / len(batches)
