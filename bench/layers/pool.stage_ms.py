"""Pool: host time packing rows for upload (`saath.pool.stage`: the
numpy re-pack of dirty rows and blanks, or of the whole slab on a
rebuild), per round (ms)."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    return program.per_round_ms(ctx, p and p.span_total("saath.pool.stage"))
