"""Share of the roofline of the [Train] step and its fill: the least time
the chip could take (the larger of FLOPs over the bf16 peak and bytes over
the HBM peak, counted from shapes, the benchmark's own ids and the fill
counter) over the device time of those programs (``train_device_ms``)."""
import trace_reduce

TRAIN = r"dlrm_\w*train_step"
FILL = r"^jit_fill\b"


def read(ctx):
    if ctx.trace is None or not ctx.steps or ctx.n_unique_mean is None:
        return None
    n, s = trace_reduce.module_seconds(ctx.trace, TRAIN)
    s += trace_reduce.module_seconds(ctx.trace, FILL)[1]
    if not n or s <= 0:
        return None
    n_fill = ctx.counters.get("cache.fills", 0) / ctx.steps
    flops, bytes_ = ctx.ref.train_step_cost(ctx.cfg, ctx.n_unique_mean, n_fill)
    least = max(flops / ctx.peaks["bf16_flops"], bytes_ / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s / n)
