"""[Exchange]'s copy of the missed rows into their pad bucket, per traced
step: self time of the ``exchange.pad`` span."""


def read(ctx):
    if "exchange.pad" not in ctx.spans:
        return None
    return ctx.span_ms_per_step("exchange.pad")
