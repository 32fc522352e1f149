"""Mean rows per batch the scheduler dispatched after warm-up (its
dispatch events)."""


def read(ctx):
    sizes = [len(rows) for rows in ctx.batches]
    return sum(sizes) / len(sizes) if sizes else None
