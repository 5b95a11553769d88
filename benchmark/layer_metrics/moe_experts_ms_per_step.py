"""Device time a step of the experts' work (scope `hvd.moe.experts`):
the grouped products over the experts held here and the shared expert;
forward and backward, all routed layers."""
from benchmark.layer_metrics import _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _scopes.MOE_EXPERTS)
