"""Share of the rows that cross the host-device link which are padding:
1 - (filled + evicted rows) / (rows put h2d + rows copied d2h), from the
runtime's ``cache.fills``, ``cache.evicts``, ``cache.h2d_rows`` and
``cache.d2h_rows`` counters over the traced window."""


def read(ctx):
    c = ctx.counters
    if "cache.h2d_rows" not in c or "cache.d2h_rows" not in c:
        return None
    moved = c["cache.h2d_rows"] + c["cache.d2h_rows"]
    if not moved:
        return None
    real = c.get("cache.fills", 0) + c.get("cache.evicts", 0)
    return 100.0 * (1.0 - real / moved)
