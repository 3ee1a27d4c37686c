"""Device-to-host read rate of [Exchange], in GB/s: the bytes of the padded
victim reads copied back (counter ``cache.d2h_bytes``) over the self time
of the ``exchange.d2h`` span. That span holds the read and the copy
together, waits included: under the sync executor it also waits for the
device work queued ahead of the read. So this is the rate at which the
program gets its victims back, not the link's rate."""


def read(ctx):
    if "cache.d2h_bytes" not in ctx.counters or "exchange.d2h" not in ctx.spans:
        return None
    s = ctx.spans["exchange.d2h"]
    return ctx.counters["cache.d2h_bytes"] / s / 1e9 if s > 0 else None
