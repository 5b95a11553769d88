"""Device time a step under the scope `hvd.attn.kda`: a delta-rule
attention's own work between its projections (the short convolutions,
the norms and gates, the chunked recurrence's kernels), forward,
recomputed forward and backward, all such layers."""
from benchmark.layer_metrics import _kda, _scopes


def compute(ctx):
    return _scopes.ms_per_step(ctx, _kda.ATTN_KDA)
