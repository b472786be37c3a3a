"""Share of the traced window in which no op ran on the device (the
engine loop between steps)."""
from chipbench import trace


def read(ctx):
    return trace.idle_share(ctx.trace)
