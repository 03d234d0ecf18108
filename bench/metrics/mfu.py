"""Model FLOP/s utilization of the whole training step, in percent: the
FLOPs a token requires (the configuration's reference ``flops_per_token``,
recompute not counted) times the window's tokens per second, over the
chips' bf16 peak from ``bench/peaks.json``."""


def read(ctx):
    if not ctx.tokens_per_s:
        return None
    return (100.0 * ctx.flops_per_token * ctx.tokens_per_s
            / (ctx.chips * ctx.peak["bf16_flops"]))
