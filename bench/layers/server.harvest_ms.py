"""Front door: host time draining completions into the tenants' buffers
(`saath.server.harvest`, the row gathers included), per round (ms)."""
from bench import program


def read(ctx):
    p = program.of(ctx)
    return program.per_round_ms(ctx,
                                p and p.span_total("saath.server.harvest"))
