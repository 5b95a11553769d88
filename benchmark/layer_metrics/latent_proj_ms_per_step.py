"""Device time a step of a latent attention's own work around the
kernel (scope `hvd.attn.latent`): the five projections, the two inner
norms, the rotary positions; forward and backward, all layers."""
from benchmark.layer_metrics import _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _scopes.ATTN_LATENT)
