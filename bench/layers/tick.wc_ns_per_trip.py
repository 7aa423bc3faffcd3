"""Device: time of the work-conservation fill per trip of its loop (ns):
the `saath.tick.wc_fill` scope's device time over the `wc_trips` the
pool counted in the traced rounds."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    busy = p and p.scope_busy("saath.tick.wc_fill")
    trips = ctx.io1.get("wc_trips", 0) - ctx.io0.get("wc_trips", 0)
    if busy is None or trips <= 0:
        return None
    return busy / trips * 1e9
