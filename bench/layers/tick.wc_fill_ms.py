"""Device: time of the tick's work-conservation fill (the ops of scope
`saath.tick.wc_fill`: the serial per-flow loop), per round (ms)."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    return program.per_round_ms(ctx,
                                p and p.scope_busy("saath.tick.wc_fill"))
