"""Pool: host time blocked on a dispatch's control download
(`saath.pool.sync_ctl`), per round (ms)."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    return program.per_round_ms(ctx,
                                p and p.span_total("saath.pool.sync_ctl"))
