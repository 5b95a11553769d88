"""Device time a step of the ops under `transpose(jvp(<Model>))/...`: the
backward pass of the blocks, with the AdamW updates XLA fuses behind
the weight-gradient matmuls."""


def compute(ctx):
    return ctx.regions.metrics()["backward_ms_per_step"]
