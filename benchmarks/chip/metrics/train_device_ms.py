"""Device time per step of the [Train] programs (``dlrm_*train_step*``)
and the scratchpad fill (``fill``), from the XLA Modules line of the trace.
Steps are counted as the [Train] programs that ran in the window."""
import trace_reduce

TRAIN = r"dlrm_\w*train_step"
FILL = r"^jit_fill\b"


def read(ctx):
    if ctx.trace is None:
        return None
    n, s = trace_reduce.module_seconds(ctx.trace, TRAIN)
    if not n:
        return None
    return (s + trace_reduce.module_seconds(ctx.trace, FILL)[1]) / n * 1e3
