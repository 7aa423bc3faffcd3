"""Pool: bytes the `SessionPool` uploaded to the device per round (kB),
from its own `io` counter over the window."""


def read(ctx):
    if not ctx.rounds:
        return None
    return (ctx.io1["upload_bytes"] - ctx.io0["upload_bytes"]) \
        / ctx.rounds / 1e3
