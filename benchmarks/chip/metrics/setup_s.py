"""Process start to the ready time of the last warm-up step: host tier,
compiles (or persistent-cache loads) and warm-up until the scratchpad
evicts (host clock)."""


def read(ctx):
    return ctx.setup_s
