"""Shared by the readers of a grouped-query model's attention
(`horovod_tpu/models/window_moe.py`): the program's scopes around its
attention calls, by layer kind, and a kind's share of its roofline.

The scopes are the program's vocabulary
(horovod_tpu/common/tracing.py, docs/tracing.md "Under jit"), copied;
tests/benchmarking compares. `hvd.attn.window` and `hvd.attn.full`
enclose the attention call of a sliding-window and of a full layer,
forward and backward: the kernels and the layout copies around them.
`hvd.attn.proj` encloses the projections, the rotary positions and the
gate, not the call. A program without a scope (the parent of the PR
that added it) has nothing to read: None, and the metric is left out.

The shapes of a call are the configuration's (`"attention_calls"` in
its file, `flops_window_moe.py` describes the key): the entry with a
window is the sliding layers', the one without the full layers'.
"""
from benchmark import flops, flops_window_moe
from benchmark.layer_metrics import _scopes

ATTN_PROJ = "hvd.attn.proj"
ATTN_WINDOW = "hvd.attn.window"
ATTN_FULL = "hvd.attn.full"


def roofline_percent(ctx, scope: str, windowed: bool):
    """100 x the least time the chip could take for one step's calls of
    one kind (forward + backward, each by the larger of its FLOPs over
    the peak and its bytes over the bandwidth,
    `flops_window_moe.attention_call_cost`) over the traced time under
    the kind's scope. The same work whatever implements it: calls run
    again for recomputation are time, not work."""
    measured = _scopes.ms_per_step(ctx, scope)
    calls = [c for c in ctx.cell.config.get("attention_calls", [])
             if (c["window"] is not None) == windowed]
    if measured is None or not calls:
        return None
    least = 0.0
    for call in calls:
        for backward in (False, True):
            seconds, bound = flops.least_seconds(
                *flops_window_moe.attention_call_cost(
                    ctx.cell.traffic["batch_per_chip"],
                    ctx.cell.traffic["seq"], call["heads"], call["kv_heads"],
                    call["head_dim"], call["window"], backward), ctx.peaks)
            print(f"info: {scope} {'backward' if backward else 'forward'}: "
                  f"{bound}-bound, least {seconds * 1e3:.3f} ms a call, "
                  f"{call['calls_per_step']} calls a step", flush=True)
            least += seconds * call["calls_per_step"]
    return 100.0 * least * 1e3 / measured
