"""Device time a step of ops without a name stack: async copies and
slices, and what a user's step leaves unnamed."""


def compute(ctx):
    return ctx.regions.metrics()["unscoped_device_ms_per_step"]
