"""The sliding-window layers' attention calls' share of their roofline:
least time from the visible (query, key) pairs and the tensors that
cross HBM once (`_attention_calls.roofline_percent`) over the traced
time under `hvd.attn.window`."""
from benchmark.layer_metrics import _attention_calls


def compute(ctx):
    return _attention_calls.roofline_percent(
        ctx, _attention_calls.ATTN_WINDOW, windowed=True)
