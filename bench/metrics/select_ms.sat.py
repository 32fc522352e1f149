"""Host milliseconds per batch inside the server's ``_select`` (α shift,
cost bucketing and the ε-constrained knapsack), over the window."""


def read(ctx):
    return ctx.span_ms("bench.select")
