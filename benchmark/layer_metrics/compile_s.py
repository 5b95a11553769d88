"""Seconds the first call of each phase's step took: trace, lower and
compile, or the load from the persistent cache, plus one step. Host
clock of the benchmark's loop; the largest part of `setup_s` that the
program decides (a step that holds a host callback is never cached)."""


def compute(ctx):
    return sum(phase.first_call_s for phase in ctx.phases.values())
