"""Fused tokens handed to clients' futures inside the window, over the
whole window's seconds."""

from harness import window


def read(ctx):
    return window.tokens_per_s(ctx.tokens, *ctx.window)
