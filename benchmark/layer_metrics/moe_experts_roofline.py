"""The held routed experts' share of their roofline: the least time the
chip could take for the two grouped products of every routed layer at
the rows an even routing sends here (`flops_moe.grouped_product_cost`,
forward and backward, each by the larger of its FLOPs over the peak and
its bytes over the bandwidth), over the traced time of those products:
the ops under `hvd.moe.experts` outside the shared expert. The same
work whatever implements it; products run again for recomputation are
time, not work."""
from benchmark import flops, flops_moe
from benchmark.layer_metrics import _scopes

SHARED_EXPERT = "/shared/"


def compute(ctx):
    measured = _scopes.ms_per_step(ctx, _scopes.MOE_EXPERTS,
                                   without=(SHARED_EXPERT,))
    if measured is None:
        return None
    dims = ctx.cell.dims
    tokens = ctx.cell.traffic["batch_per_chip"] * ctx.cell.traffic["seq"]
    rows = tokens * flops_moe.expected_expert_rows_per_token(dims)
    _, layers, _ = flops_moe.blocks(dims)
    least = 0.0
    for backward in (False, True):
        seconds, bound = flops.least_seconds(
            *flops_moe.grouped_product_cost(rows, dims, backward), ctx.peaks)
        print(f"info: routed experts {'backward' if backward else 'forward'}:"
              f" {bound}-bound, least {seconds * 1e3:.3f} ms a layer at "
              f"{rows:.0f} rows, {layers} layers", flush=True)
        least += seconds * layers
    return 100.0 * least * 1e3 / measured
