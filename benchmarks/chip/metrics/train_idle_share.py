"""Share of the traced window in which no op ran on the device (trainer
loop and input), averaged over the chips."""
from chipbench import trace


def read(ctx):
    return trace.idle_share(ctx.trace)
