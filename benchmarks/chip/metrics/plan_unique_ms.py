"""[Plan]'s ``np.unique`` of each batch's ids, per traced step: self time
of the host planner's ``plan.unique`` span."""


def read(ctx):
    if "plan.unique" not in ctx.spans:
        return None
    return ctx.span_ms_per_step("plan.unique")
