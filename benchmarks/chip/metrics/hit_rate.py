"""Share of each step's unique rows already in the scratchpad at [Plan]:
the runtime's ``cache.hits`` over ``cache.unique`` counters, traced window."""


def read(ctx):
    u = ctx.counters.get("cache.unique", 0)
    if not u:
        return None
    return 100.0 * ctx.counters.get("cache.hits", 0) / u
