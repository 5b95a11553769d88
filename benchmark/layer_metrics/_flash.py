"""Shared by the flash kernel's readers: the kernels' names as the
program gives them (`name=` in ops/flash_attention.py) and their least
time on the chip from shapes. The shapes of one call are the
configuration's (`"attention"` in its file: heads, the two head sizes,
calls a step), not derived from the model's width."""
from benchmark import flops

FORWARD = "flash_attention_fwd"
BACKWARD = "flash_attention_bwd"


def roofline_percent(ctx, kernel: str, backward: bool):
    """100 x least time over measured time of one kernel, per step; None
    where the kernel did not run."""
    measured = ctx.tables.seconds_of(kernel)
    if measured is None:
        return None
    shape = ctx.cell.config["attention"]
    work = flops.attention_kernel_cost(
        batch=ctx.cell.traffic["batch_per_chip"], seq=ctx.cell.traffic["seq"],
        heads=shape["heads"], qk_head_dim=shape["qk_head_dim"],
        v_head_dim=shape["v_head_dim"], causal=ctx.cell.dims["causal"],
        backward=backward)
    least, bound = flops.least_seconds(*work, ctx.peaks)
    calls = shape["calls_per_step"]
    print(f"info: {kernel}: {bound}-bound, least {least * 1e3:.3f} ms a "
          f"call, {calls} calls a step", flush=True)
    return 100.0 * least * calls * ctx.tables.steps / measured
