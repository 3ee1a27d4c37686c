"""The program's own wait for the data stream, per traced step: self time
of the runtime's ``input`` span (``next()`` on the stream and the
look-ahead peek)."""


def read(ctx):
    if "input" not in ctx.spans:
        return None
    return ctx.span_ms_per_step("input")
