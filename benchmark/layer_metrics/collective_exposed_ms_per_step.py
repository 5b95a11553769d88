"""The part of `collective_ms_per_step` during which no other op ran on
chip 0: what the collectives add to the step."""


def compute(ctx):
    return ctx.tables.collective_exposed_s / ctx.tables.steps * 1e3
