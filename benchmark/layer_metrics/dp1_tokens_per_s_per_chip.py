"""Throughput of the baseline phase (the same step on one chip, same
batch per chip): the denominator of `scaling_efficiency`. Host clock;
the traced intervals lie in the main phase and touch it not."""


def compute(ctx):
    baseline = ctx.phases.get("baseline")
    return None if baseline is None else baseline.tokens_per_s_per_chip
