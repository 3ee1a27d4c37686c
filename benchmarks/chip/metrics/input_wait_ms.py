"""Time the pipeline waited for the benchmark producer's next batch, per
traced step (host clock around taking a batch)."""


def read(ctx):
    if not ctx.steps:
        return None
    return ctx.input_wait_s / ctx.steps * 1e3
