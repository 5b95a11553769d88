"""Median host time of `hvd.wrap_step.prepare`: the wrapper's own flatten,
key and lookup before its jitted call. None where no step goes through
`wrap_step`."""


def compute(ctx):
    return ctx.regions.metrics()["wrap_step_prepare_host_ms"]
