"""Median host time of the program's `hvd.step` span, the step call as
the program sees it."""


def compute(ctx):
    return ctx.regions.metrics()["step_call_host_ms"]
