"""Whole training step's share of the chips' bf16 peak: model operations
per token (the configuration reference's ``train_flops_per_token``) times
the tokens per second of the traced run's untraced remainder, over chips
times peak."""

NEEDS = ("train_flops_per_token",)


def read(ctx):
    c = ctx.counters
    if not c.get("tokens_per_s"):
        return None
    per_token = ctx.cell.reference.train_flops_per_token(
        ctx.config, c["seq_len"], c["n_pred"])
    return 100.0 * per_token * c["tokens_per_s"] / (
        c["chips"] * ctx.peak["bf16_flops_per_s"])
