"""Device loop and tick: device busy time per round (ms)."""


def read(ctx):
    if not ctx.rounds or not ctx.busy:
        return None
    return ctx.busy_s / ctx.rounds * 1e3
