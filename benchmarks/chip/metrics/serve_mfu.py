"""Whole serving step's share of the chip's bf16 peak: model operations
of the prompts prefilled and the tokens decoded in the window
(counts.prefill_flops, counts.decode_token_flops) over the window."""


def read(ctx):
    c = ctx.counters
    if not c.get("flops"):
        return None
    return 100.0 * c["flops"] / (c["seconds"] * ctx.peak["bf16_flops_per_s"])
