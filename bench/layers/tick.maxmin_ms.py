"""Device: time of the tick's max-min work-conservation fill (the ops
of scope `saath.tick.maxmin`: the incidence one-hots and the max-min
kernel), per round (ms): the union of its ops on each chip, averaged
over the chips. None where no op carries the scope (a program without
it, or a cell without the max-min fill)."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    busy = p and p.scope_busy("saath.tick.maxmin")
    return program.per_round_ms(ctx, busy or None)
