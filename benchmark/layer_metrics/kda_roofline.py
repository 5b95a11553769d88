"""The delta-rule layers' share of their roofline: least time from the
chunked form's FLOPs and the tensors that cross HBM once
(`flops_linear_moe.kda_cost`, `_kda.roofline_percent`) over the traced
time under `hvd.attn.kda`."""
from benchmark.layer_metrics import _kda


def compute(ctx):
    return _kda.roofline_percent(ctx)
