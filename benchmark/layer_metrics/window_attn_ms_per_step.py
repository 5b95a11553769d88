"""Device time a step under the scope `hvd.attn.window`: the attention
calls of the sliding-window layers, forward and backward with the
forward calls a recomputing step runs again: the kernels and the layout
copies around them."""
from benchmark.layer_metrics import _attention_calls, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _attention_calls.ATTN_WINDOW)
