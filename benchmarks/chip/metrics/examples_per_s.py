"""Examples of every step that finished in the timed window, over the
window: from the ready time of the last warm-up step to that of the last
step (host clock)."""


def read(ctx):
    if not ctx.timed_steps or ctx.timed_s <= 0:
        return None
    return ctx.timed_steps * ctx.batch / ctx.timed_s
