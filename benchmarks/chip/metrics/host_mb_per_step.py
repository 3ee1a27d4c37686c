"""Host tier bytes read and written per traced step (the host table's
byte counters: [Collect] gathers and write-backs)."""


def read(ctx):
    if not ctx.steps:
        return None
    return ctx.host_bytes / ctx.steps / 1e6
