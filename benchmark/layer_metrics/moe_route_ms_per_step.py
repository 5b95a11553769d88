"""Device time a step of routing (scope `hvd.moe.route`): router, top-k,
gates, the sort into expert order, the gather into the dispatch buffer
and the weighted gather back; forward and backward, all routed
layers."""
from benchmark.layer_metrics import _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _scopes.MOE_ROUTE)
