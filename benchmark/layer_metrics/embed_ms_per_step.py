"""Device time a step of the token (and position) embedding (scope
`hvd.embed`): the gather forward, the scatter into the table backward."""
from benchmark.layer_metrics import _blocks, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _blocks.EMBED)
