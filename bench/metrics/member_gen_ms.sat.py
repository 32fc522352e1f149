"""Host milliseconds per batch inside the server's ``_generate_members``,
over the window."""


def read(ctx):
    return ctx.span_ms("bench.members")
