"""Device time a step of the scope `hvd.loss` and the head's modules,
forward and backward."""


def compute(ctx):
    return ctx.regions.metrics()["loss_head_ms_per_step"]
