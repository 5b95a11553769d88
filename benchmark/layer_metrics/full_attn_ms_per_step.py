"""Device time a step under the scope `hvd.attn.full`: the attention
calls of the full (global) layers of a model that also has
sliding-window ones, forward and backward with the forward calls a
recomputing step runs again: the kernels and the layout copies around
them."""
from benchmark.layer_metrics import _attention_calls, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _attention_calls.ATTN_FULL)
