"""mfu: the whole train step's share of the chips' bf16 peak: model FLOPs
per token (the configuration's formula, recomputation not counted) times
the tokens per second of the traced window, over chips times the peak of
``peaks.json``. Layer: whole train step. Moves tokens_per_s."""


def read(ctx):
    w = ctx.window
    if not w.steps or w.seconds <= 0:
        return None
    rate = w.steps * ctx.tokens_per_step / w.seconds
    return 100.0 * ctx.flops_per_token * rate / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
