"""Device milliseconds a step spends in the Pallas attention kernels,
forward and backward, all layers: their events in the trace, found by
kernel name. Absent where the kernels do not run."""
from benchmark.layer_metrics import _flash


def compute(ctx):
    seconds = ctx.tables.seconds_of(_flash.FORWARD, _flash.BACKWARD)
    return None if seconds is None else seconds / ctx.tables.steps * 1e3
