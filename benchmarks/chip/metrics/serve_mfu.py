"""Whole serving step's share of the chip's bf16 peak: model operations
of the prompts prefilled and the tokens decoded in the window (the
configuration reference's ``prefill_flops`` and ``decode_token_flops``)
over the window."""

NEEDS = ("prefill_flops", "decode_token_flops")


def read(ctx):
    c = ctx.counters
    if not c.get("served"):
        return None
    ref, cfg = ctx.cell.reference, ctx.config
    flops = 0.0
    for plen, j in c["served"]:
        if j == 0:
            flops += ref.prefill_flops(cfg, plen)
        else:
            flops += ref.decode_token_flops(cfg, plen + j)
    return 100.0 * flops / (c["seconds"] * ctx.peak["bf16_flops_per_s"])
