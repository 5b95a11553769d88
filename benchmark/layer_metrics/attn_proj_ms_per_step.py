"""Device time a step of a grouped-query attention's own work around
the attention call (scope `hvd.attn.proj`): the q / k / v / gate /
output projections, the rotary positions and the gate's product;
forward and backward, all layers."""
from benchmark.layer_metrics import _attention_calls, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _attention_calls.ATTN_PROJ)
