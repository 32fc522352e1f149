"""Compile requests (persistent-cache hits and misses alike) that JAX
reported inside the window."""


def read(ctx):
    return ctx.compiles.count(*ctx.window)
