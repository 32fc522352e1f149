"""95th percentile, in ms, of every gap between consecutive streamed
tokens of every request, both tokens inside the window."""

from harness import window


def read(ctx):
    gaps = window.inter_token_gaps(ctx.per_request, *ctx.window)
    return 1e3 * window.percentile(gaps, 95) if gaps else None
