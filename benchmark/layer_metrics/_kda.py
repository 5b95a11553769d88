"""Shared by the readers of a delta-rule attention
(`horovod_tpu/models/linear_moe.py::DeltaAttention`): the program's scope
around its own work and that work's share of its roofline.

The scope is the program's vocabulary (horovod_tpu/common/tracing.py,
docs/tracing.md "Under jit"), copied; tests/benchmarking compares.
`hvd.attn.kda` encloses everything the block does between its
projections, forward, recomputed forward and backward: the short
convolutions, the norms and gates, and the chunked recurrence's kernels
`kda_fwd` / `kda_bwd`. A program without the scope (the parent of the
PR that added it) has nothing to read: None, and the metric is left
out.
"""
from benchmark import flops, flops_linear_moe
from benchmark.layer_metrics import _scopes

ATTN_KDA = "hvd.attn.kda"


def roofline_percent(ctx):
    """100 x the least time the chip could take for one step's
    delta-rule calls (forward + backward, each by the larger of its
    FLOPs over the peak and its bytes over the bandwidth,
    `flops_linear_moe.kda_cost` at the program's chunk) over the
    traced time under the scope. The same work whatever implements it:
    calls run again for recomputation, and the elementwise work around
    the kernels, are time, not work."""
    measured = _scopes.ms_per_step(ctx, ATTN_KDA)
    dims = ctx.cell.dims
    if measured is None or "linear_attn_config" not in dims:
        return None
    calls = [mixer for mixer, _ in flops_linear_moe.kinds(dims)].count("kda")
    least = 0.0
    for backward in (False, True):
        seconds, bound = flops.least_seconds(*flops_linear_moe.kda_cost(
            dims, ctx.cell.traffic["seq"], ctx.cell.traffic["batch_per_chip"],
            backward), ctx.peaks)
        print(f"info: {ATTN_KDA} {'backward' if backward else 'forward'}: "
              f"{bound}-bound, least {seconds * 1e3:.3f} ms a call, "
              f"{calls} calls a step", flush=True)
        least += seconds * calls
    return 100.0 * least * 1e3 / measured
