"""Device time a step of the scope `hvd.optimizer`: the updates left in
fusions of their own."""


def compute(ctx):
    return ctx.regions.metrics()["optimizer_ms_per_step"]
