"""Shared by the flash kernel's readers: the kernels' names as the
program gives them (`name=` in ops/flash_attention.py) and their least
time on the chip from shapes."""
from benchmark import flops

FORWARD = "flash_attention_fwd"
BACKWARD = "flash_attention_bwd"


def roofline_percent(ctx, kernel: str, backward: bool):
    """100 x least time over measured time of one kernel, per step; None
    where the kernel did not run."""
    measured = ctx.tables.seconds_of(kernel)
    if measured is None:
        return None
    dims = ctx.cell.dims
    work = flops.attention_kernel_cost(
        batch=ctx.cell.traffic["batch_per_chip"], seq=ctx.cell.traffic["seq"],
        heads=dims["n_heads"], head_dim=dims["d_model"] // dims["n_heads"],
        causal=dims["causal"], backward=backward)
    least, bound = flops.least_seconds(*work, ctx.peaks)
    print(f"info: {kernel}: {bound}-bound, least {least * 1e3:.3f} ms a "
          f"call, {dims['n_layers']} calls a step", flush=True)
    return 100.0 * least * dims["n_layers"] * ctx.tables.steps / measured
