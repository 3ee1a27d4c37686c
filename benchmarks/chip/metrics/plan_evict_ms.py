"""[Plan]'s eviction per traced step: the eligibility sweep over the
slots, the future holds and the victim selection (self time of the host
planner's ``plan.evict`` span, less the ``np.unique`` nested in it)."""


def read(ctx):
    if "plan.evict" not in ctx.spans:
        return None
    return ctx.span_ms_per_step("plan.evict")
