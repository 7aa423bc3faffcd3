"""Device: flows the work-conservation fill gave a rate, per round: the
`wc_fills` the pool counted in the traced rounds (the fill's loop trips;
a program without the counter reads None)."""


def read(ctx):
    if not ctx.rounds or "wc_fills" not in ctx.io1:
        return None
    return (ctx.io1["wc_fills"] - ctx.io0["wc_fills"]) / ctx.rounds
