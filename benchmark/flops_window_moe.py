"""What a training step of the grouped-query decoder with sliding-window
and full layers (`configs/window_moe/laguna-xs2.json`) has to compute,
from shapes alone, for the share of the model one chip holds. The rules
are `flops.py`'s and `flops_moe.py`'s: forward + backward = 3 x forward,
2 P a token for a parameter matrix of P entries, nothing recomputed,
nothing elementwise, the routed experts at the rows an even routing
sends to the experts held here. `dims` are the model's keyword
arguments.

Attention is counted by the (query, key) pairs the mask leaves visible,
2 (Dqk + Dv) FLOPs a pair and query head in the forward pass: a causal
sequence of S positions has S (S + 1) / 2, a window W leaves
W S - W (W - 1) / 2 of them (every query sees W keys but the first
W - 1, which see one to W - 1). Key/value heads shared by a group of
query heads change the bytes, not the FLOPs.

`attention_calls`, in the configuration's file, lists the shapes of the
attention calls of one step, one entry a shape: `heads`, `kv_heads`,
`head_dim`, `window` (null: none) and `calls_per_step` (the layers of
that kind: what the model needs, not what a recomputing step executes).
The two roofline readers (`layer_metrics/window_attention_roofline.py`,
`full_attention_roofline.py`) take the entry with and without a window.
"""
from __future__ import annotations

from typing import Optional

from benchmark import flops_moe

SPARSE = "sparse"
SLIDING = "sliding_attention"


def layers(dims: dict) -> list:
    """(attention kind, query heads, feed-forward kind) of every layer
    the program holds: the first `num_hidden_layers` entries of the
    three per-layer lists."""
    return list(zip(dims["layer_types"], dims["num_attention_heads_per_layer"],
                    dims["mlp_layer_types"]))[:dims["num_hidden_layers"]]


def attention_params(dims: dict, heads: int) -> int:
    """q, k, v, the per-head gate and the output projection of one
    layer with `heads` query heads."""
    d, dh = dims["hidden_size"], dims["head_dim"]
    return (2 * d * heads * dh + 2 * d * dims["num_key_value_heads"] * dh
            + d * heads)


def visible_pairs(seq: int, window: Optional[int]) -> float:
    """(query, key) pairs of one causal sequence a query head scores."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return window * seq - window * (window - 1) / 2


def _outside_routed_experts(dims: dict) -> int:
    """Entries a token is multiplied with outside the routed experts'
    grouped products: attention's projections, the dense feed-forward,
    routers, shared experts, the head."""
    d = dims["hidden_size"]
    total = d * dims["vocab_size"]
    for _, heads, mlp in layers(dims):
        total += attention_params(dims, heads)
        if mlp == SPARSE:
            total += (d * dims["n_routed_experts"]
                      + 3 * d * dims["shared_expert_intermediate_size"])
        else:
            total += 3 * d * dims["intermediate_size"]
    return total


def _routed_layers(dims: dict) -> int:
    return sum(mlp == SPARSE for _, _, mlp in layers(dims))


def per_token(dims: dict, seq: int) -> float:
    """Model FLOPs per token of one training step: `mfu`'s numerator."""
    matmul = _outside_routed_experts(dims) + (
        _routed_layers(dims) * flops_moe.expected_expert_rows_per_token(dims)
        * flops_moe.expert_params(dims))
    attention = sum(
        4 * dims["head_dim"] * heads * visible_pairs(
            seq, dims["sliding_window"] if kind == SLIDING else None) / seq
        for kind, heads, _ in layers(dims))
    return 3.0 * (2 * matmul + attention)


def attention_call_cost(batch: int, seq: int, heads: int, kv_heads: int,
                        head_dim: int, window: Optional[int], backward: bool,
                        itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one causal attention call needs, forward or
    backward, whatever implements it.

    FLOPs: 2 (Dqk + Dv) a visible pair, query head and batch row in the
    forward pass (scores and the weighted sum), twice that backward (dQ
    and dK at Dqk, dV and dP at Dv); the score recomputation a flash
    backward does is the kernel's choice and is not counted.

    Bytes are the tensors that must cross HBM once: forward reads Q at
    `heads` and K, V at `kv_heads` and writes O and the per-row
    logsumexp (float32); backward reads Q, O, dO and the logsumexp at
    `heads` and K, V at `kv_heads`, and writes dQ at `heads` and dK, dV
    at `kv_heads`."""
    pairs = visible_pairs(seq, window)
    passes = 2 if backward else 1
    flops_ = passes * 2.0 * (2 * head_dim) * batch * heads * pairs
    tensor = batch * seq * head_dim * itemsize
    rows = batch * heads * seq * 4
    if backward:
        bytes_ = 4 * heads * tensor + 4 * kv_heads * tensor + rows
    else:
        bytes_ = 2 * heads * tensor + 2 * kv_heads * tensor + rows
    return flops_, float(bytes_)


def matmul_params(dims: dict) -> float:
    """What the v5e compile test holds XLA's own FLOP count to, as
    entries a token is multiplied with at 6 FLOPs an entry
    (`flops_moe.matmul_params` says what XLA sees: the products outside
    custom calls, the grouped products at the dispatch buffer's rows,
    and with `remat` a block's forward products once more but the last
    of its dense or shared feed-forward)."""
    d = dims["hidden_size"]
    buffered = dims["num_experts_per_tok"] * flops_moe.expert_params(dims)
    visible = _outside_routed_experts(dims) + _routed_layers(dims) * buffered
    if not dims.get("remat"):
        return visible
    again = 0
    for _, heads, mlp in layers(dims):
        again += attention_params(dims, heads)
        if mlp == SPARSE:
            again += (d * dims["n_routed_experts"]
                      + 2 * d * dims["shared_expert_intermediate_size"]
                      + buffered)
        else:
            again += 2 * d * dims["intermediate_size"]
    return visible + again / 3
