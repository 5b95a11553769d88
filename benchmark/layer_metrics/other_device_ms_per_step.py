"""Device milliseconds a step spends outside the attention kernels and
the collectives: the blocks' matmul and elementwise fusions, the head
and loss, the optimizer update."""
from benchmark import trace_reduce
from benchmark.layer_metrics import _flash


def compute(ctx):
    seconds = sum(
        s for name, s in ctx.tables.op_seconds.items()
        if name not in (_flash.FORWARD, _flash.BACKWARD)
        and not trace_reduce.is_collective(name))
    return seconds / ctx.tables.steps * 1e3
