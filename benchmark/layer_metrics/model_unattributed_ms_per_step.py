"""Device time a step of the models layer that the program does not
name: the ops `trace_regions.region_of` files under `forward` or
`backward` (the head and loss, and the optimizer, have metrics of their
own) whose name stack holds no `hvd.` scope and that are no kernel of
the configuration's `kernels` (op families, each with a metric by its
name). Residual adds and casts where every part is named; an `info:`
line lists the heaviest. None for a program that names none of
`_blocks.py`'s parts."""
import collections
import re

from benchmark import trace_reduce, trace_regions
from benchmark.layer_metrics import _blocks, _scopes

MODEL_REGIONS = ("forward", "backward")


def unattributed(ops, window: tuple, steps: int, kernels, top: int = 5):
    """(seconds a step, heaviest) of those ops in `window`; `heaviest`:
    up to `top` (name stack with the layer index blanked, op family,
    seconds a step), largest first. None where no op carries one of
    `_blocks.py`'s scopes."""
    lo, hi = window
    named, by_name = False, collections.Counter()
    for op in ops:
        if op.end <= lo or op.start >= hi:
            continue
        named = named or any(scope in op.tf_op for scope in (
            _blocks.MLP, _blocks.NORM, _blocks.EMBED))
        family = trace_reduce.family(op.name)
        if (trace_regions.region_of(op.tf_op) in MODEL_REGIONS
                and trace_regions.PROGRAM_PREFIX not in op.tf_op
                and family not in kernels):
            stack = re.sub(r"layer_\d+", "layer_*", op.tf_op.rstrip(":"))
            by_name[stack, family] += min(op.end, hi) - max(op.start, lo)
    if not named:
        return None
    return (sum(by_name.values()) / steps,
            [(stack, family, s / steps)
             for (stack, family), s in by_name.most_common(top)])


def compute(ctx):
    if ctx.trace_file is None:
        return None
    trace = _scopes._load(ctx.trace_file)
    starts = trace_reduce.step_starts(trace.programs,
                                      ctx.cell.traffic["log_every"])
    found = unattributed(trace.ops, (starts[0], starts[-1]),
                         len(starts) - 1, ctx.cell.config["kernels"])
    if found is None:
        return None
    seconds, heaviest = found
    for stack, family, s in heaviest:
        print(f"info: model_unattributed: {s * 1e3:.3f} ms a step "
              f"{family} {stack}", flush=True)
    return seconds * 1e3
