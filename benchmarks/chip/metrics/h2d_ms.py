"""[Exchange]'s host-to-device put of the padded rows, per traced step:
self time of the ``exchange.h2d`` span around ``jax.device_put``. That is
the put's dispatch and hand-over on the host; ``device_put`` returns before
the copy to the device ends, so the transfer itself is not in it."""


def read(ctx):
    if "exchange.h2d" not in ctx.spans:
        return None
    return ctx.span_ms_per_step("exchange.h2d")
