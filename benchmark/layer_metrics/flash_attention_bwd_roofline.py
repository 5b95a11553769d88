"""The backward attention kernel's share of its roofline, counting the
four matmuls the gradient needs and not the kernel's recomputation of
the scores (benchmark/flops.py)."""
from benchmark.layer_metrics import _flash


def compute(ctx):
    return _flash.roofline_percent(ctx, _flash.BACKWARD, backward=True)
