"""Device time a step of the blocks' dense feed-forward layers (scope
`hvd.mlp`): their products, activation and dropout, forward and
backward, all layers; the AdamW update XLA fuses behind a
weight-gradient product counts with it. Not a routed layer's shared
expert (`moe_experts_ms_per_step`)."""
from benchmark.layer_metrics import _blocks, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _blocks.MLP)
