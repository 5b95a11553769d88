"""Device time a step of the norms (scope `hvd.norm`): every block's two,
the final norm, a prediction module's, forward and backward, and what
XLA fuses under their roots. Not a latent attention's inner norms
(`latent_proj_ms_per_step`)."""
from benchmark.layer_metrics import _blocks, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _blocks.NORM)
