"""Milliseconds a step has a collective in flight on chip 0 (all-reduce,
reduce-scatter, all-gather, ... by XLA's op names), hidden or not."""


def compute(ctx):
    return ctx.tables.collective_s / ctx.tables.steps * 1e3
