"""round.mfu: the round's model operations per second over the chips'
peak.  Model operations per token come from ``flops/round.py`` (no
recomputation counted); tokens per second are the local-training tokens
of the traced window's rounds over its length."""


def read(ctx):
    per_token = ctx.flops("round").flops_per_token(
        ctx.config, ctx.workload["round"]["seq_len"])
    return 100.0 * per_token * ctx.tokens_per_s / (
        ctx.chips * ctx.peak["bf16_flops_per_s"])
