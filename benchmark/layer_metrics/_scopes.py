"""Shared by the readers of the program's own scopes
(horovod_tpu/common/tracing.py, docs/tracing.md "Under jit"): device
time a step of the ops whose name stack holds a scope, forward and
backward, in the window of whole steps that `trace_regions.reduce`
takes. The traced run's file is read once more for them (the `Regions`
a reader reaches as `ctx.regions` keep no single op), once for all the
readers here. A program without the scope (the parent of the PR that
added it) has nothing to read: None, and the metric is left out."""
import functools

from benchmark import trace_reduce, trace_regions

# The program's vocabulary, copied (tests/benchmarking compares).
ATTN_LATENT = "hvd.attn.latent"
MOE_ROUTE = "hvd.moe.route"
MOE_EXPERTS = "hvd.moe.experts"
MTP = "hvd.mtp"


@functools.lru_cache(maxsize=1)
def _load(path: str):
    return trace_regions.load(path)


def seconds_per_step(ops, window: tuple, steps: int, scope: str,
                     without: tuple = ()):
    """Seconds a step of the ops in `window` whose name stack holds
    `scope` and none of `without`; None where there is none."""
    lo, hi = window
    found = [min(op.end, hi) - max(op.start, lo) for op in ops
             if scope in op.tf_op and op.end > lo and op.start < hi
             and not any(part in op.tf_op for part in without)]
    return sum(found) / steps if found else None


def ms_per_step(ctx, scope: str, without: tuple = ()):
    if ctx.trace_file is None:
        return None
    trace = _load(ctx.trace_file)
    starts = trace_reduce.step_starts(trace.programs,
                                      ctx.cell.traffic["log_every"])
    seconds = seconds_per_step(trace.ops, (starts[0], starts[-1]),
                               len(starts) - 1, scope, without)
    return None if seconds is None else seconds * 1e3
