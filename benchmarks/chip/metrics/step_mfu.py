"""Whole-step model FLOP utilisation: the model's training FLOPs per
example times examples per second over the traced window, over the chip's
bf16 peak (the matmuls of a float32 model run as bf16 passes at the
default precision)."""


def read(ctx):
    if not ctx.steps or ctx.window_s <= 0:
        return None
    flops = ctx.ref.model_flops_per_example(ctx.cfg) * ctx.batch * ctx.steps
    return 100.0 * flops / ctx.window_s / ctx.peaks["bf16_flops"]
