"""The forward attention kernel's share of its roofline: the least time
the chip could take for the causal forward's FLOPs and bytes
(benchmark/flops.py, from B, S, H, D) over the kernel's traced time."""
from benchmark.layer_metrics import _flash


def compute(ctx):
    return _flash.roofline_percent(ctx, _flash.FORWARD, backward=False)
