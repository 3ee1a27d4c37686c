"""Host tier self time per traced step: [Collect] and its host gather,
[Insert]'s host half and its write-back (program spans)."""


def read(ctx):
    names = ("collect", "collect.gather", "insert_host", "insert.writeback")
    if not any(n in ctx.spans for n in names):
        return None
    return ctx.span_ms_per_step(*names)
