"""The dense feed-forward layers' share of their roofline: the least time
the chip could take for their products at the configuration's widths
(`flops_blocks.least_seconds`, forward and backward, each product by the
larger of its FLOPs over the peak and its bytes over the bandwidth),
over the traced time under `hvd.mlp` (`mlp_ms_per_step`). The same work
whatever implements it: products run again for recomputation are time,
not work. In the gpt2 / bert cells XLA fuses the AdamW update of each
weight behind its gradient product: that time counts, its bytes are not
work of the product."""
from benchmark import flops_blocks
from benchmark.layer_metrics import _blocks, _scopes


def compute(ctx):
    measured = _scopes.ms_per_step(ctx, _blocks.MLP)
    if measured is None:
        return None
    tokens = ctx.cell.traffic["batch_per_chip"] * ctx.cell.traffic["seq"]
    rows = flops_blocks.least_seconds(ctx.cell.dims, tokens, ctx.peaks)
    for name, pass_, bound, seconds in rows:
        print(f"info: dense feed-forward {name} {pass_}: {bound}-bound, "
              f"least {seconds * 1e3:.3f} ms a step at {tokens} tokens",
              flush=True)
    return 100.0 * sum(s for *_, s in rows) * 1e3 / measured
