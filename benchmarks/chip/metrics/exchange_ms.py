"""[Exchange] self time per traced step: the h2d put and the d2h copy of
victim rows (program spans ``exchange`` and ``exchange.d2h``)."""


def read(ctx):
    names = ("exchange", "exchange.d2h")
    if not any(n in ctx.spans for n in names):
        return None
    return ctx.span_ms_per_step(*names)
