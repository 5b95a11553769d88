"""Device time a step of the ops under `jvp(<Model>)/...`: the forward
pass of the blocks, head and loss excluded."""


def compute(ctx):
    return ctx.regions.metrics()["forward_ms_per_step"]
