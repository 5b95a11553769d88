"""Device time a step of the multi-token-prediction module (scope
`hvd.mtp`, outermost: its block, its norms and projection, the shared
head applied to it); forward and backward."""
from benchmark.layer_metrics import _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _scopes.MTP)
