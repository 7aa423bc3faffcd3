"""Pool: rows the pool's row scatters wrote per round, the pad rows of
their power-of-two buckets included (`pool.io["scatter_rows"]`); None
from a program without the counter."""


def read(ctx):
    if not ctx.rounds or "scatter_rows" not in ctx.io1:
        return None
    return (ctx.io1["scatter_rows"] - ctx.io0["scatter_rows"]) / ctx.rounds
