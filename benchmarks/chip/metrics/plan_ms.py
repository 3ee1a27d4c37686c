"""[Plan] self time per traced step: the program spans ``plan`` and
``plan.materialize`` (device planner), less their nested spans."""


def read(ctx):
    names = ("plan", "plan.materialize")
    if not any(n in ctx.spans for n in names):
        return None
    return ctx.span_ms_per_step(*names)
