"""Front door: host time in `CoflowServer.submit` per round (ms)."""


def read(ctx):
    if not ctx.rounds:
        return None
    return ctx.span_total("bench.submit") / ctx.rounds * 1e3
