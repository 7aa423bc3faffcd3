"""Pool, dispatch and harvest: the part of `CoflowServer.advance` in
which no device operation runs, per round (ms)."""


def read(ctx):
    if not ctx.rounds:
        return None
    host = ctx.span_total("bench.advance") - ctx.device_busy_in("bench.advance")
    return host / ctx.rounds * 1e3
