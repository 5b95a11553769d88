"""The full layers' attention calls' share of their roofline, in a model
that also has sliding-window layers: least time from the causal
triangle's (query, key) pairs and the tensors that cross HBM once
(`_attention_calls.roofline_percent`) over the traced time under
`hvd.attn.full`."""
from benchmark.layer_metrics import _attention_calls


def compute(ctx):
    return _attention_calls.roofline_percent(
        ctx, _attention_calls.ATTN_FULL, windowed=False)
