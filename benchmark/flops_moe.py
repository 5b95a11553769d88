"""What a training step of the latent-attention mixture-of-experts
configuration (`configs/latent_moe/joyai-llm-flash.json`) has to compute, from
shapes alone, for the share of the model one chip holds. The rules are
`flops.py`'s: forward + backward = 3 x forward, 2 P a token for a
parameter matrix of P entries, causal attention halved, nothing
recomputed, nothing elementwise. `dims` are the model's keyword
arguments (the published key names).

Routed experts are counted at the rows an even routing sends to the
experts held here: a token chooses `num_experts_per_tok` of
`n_routed_experts`, of which `experts_held` are here, so it meets
`k * held / total` held experts on average (8 x 16 / 256 = 0.5). The
sum over a step is exact whenever the held experts receive their even
share of the choices; a skewed router does more or less work than this
count, and `mfu` then moves with the routing.
"""
from __future__ import annotations

from benchmark import flops


def _held(dims: dict) -> int:
    return dims.get("experts_held") or dims["n_routed_experts"]


def attention_params(dims: dict) -> int:
    """The five projections of one latent attention."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    nope, rope, dv = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                      dims["v_head_dim"])
    return (d * dims["q_lora_rank"]
            + dims["q_lora_rank"] * h * (nope + rope)
            + d * (dims["kv_lora_rank"] + rope)
            + dims["kv_lora_rank"] * h * (nope + dv)
            + h * dv * d)


def expert_params(dims: dict) -> int:
    """Gate, up and down of one expert."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def expected_expert_rows_per_token(dims: dict) -> float:
    return (dims["num_experts_per_tok"] * _held(dims)
            / dims["n_routed_experts"])


def blocks(dims: dict) -> tuple:
    """(dense blocks, routed blocks with the module's, multi-token-
    prediction modules)."""
    dense, mtp = dims["first_k_dense_replace"], dims["num_nextn_predict_layers"]
    return dense, dims["num_hidden_layers"] - dense + mtp, mtp


def _outside_routed_experts(dims: dict) -> int:
    """Entries a token is multiplied with outside the routed experts'
    grouped products: the latent projections, the dense feed-forward,
    routers, shared experts, the module's projection, the head (once
    more for the module)."""
    d = dims["hidden_size"]
    dense, routed, mtp = blocks(dims)
    return ((dense + routed) * attention_params(dims)
            + dense * 3 * d * dims["intermediate_size"]
            + routed * (d * dims["n_routed_experts"]
                        + dims["n_shared_experts"] * expert_params(dims))
            + mtp * 2 * d * d
            + (1 + mtp) * d * dims["vocab_size"])


def routed(dims: dict, seq: int) -> float:
    """Model FLOPs per token of one training step: `mfu`'s numerator."""
    _, n_routed, mtp = blocks(dims)
    matmul = _outside_routed_experts(dims) + (
        n_routed * expected_expert_rows_per_token(dims)
        * expert_params(dims))
    heads = dims["num_attention_heads"] * (
        dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
        + dims["v_head_dim"])
    attention = 2 * seq * heads * (dims["num_hidden_layers"] + mtp)
    if dims["causal"]:
        attention //= 2
    return 3.0 * (2 * matmul + attention)


def grouped_product_cost(rows: float, dims: dict, backward: bool,
                         itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one routed layer's two grouped products
    over `rows` rows in all: `rows` x (gate and up, then down). Backward
    is twice the forward (a gradient for the rows, one for the weights).
    Bytes that must cross HBM once: the held experts' weights (read;
    written once more as gradients in the backward pass), the rows in
    and out at the model's width and the activations between the two
    products at the experts' width."""
    d, f = dims["hidden_size"], dims["moe_intermediate_size"]
    passes = 2 if backward else 1
    flops_ = passes * 2.0 * rows * expert_params(dims)
    weights = _held(dims) * expert_params(dims) * itemsize
    activations = rows * (2 * d + 3 * f) * itemsize
    return flops_, float(passes * (weights + activations))


def matmul_params(dims: dict) -> float:
    """What the v5e compile test holds XLA's own FLOP count to, as
    entries a token is multiplied with at 6 FLOPs an entry: every
    product XLA can see. Those are the ones outside custom calls
    (`_outside_routed_experts`; the attention kernels are invisible to
    XLA), and the grouped products, whose Pallas calls declare a cost
    estimate of 2 x buffer rows x K x N each: XLA counts them at the
    rows of the dispatch buffer, `num_experts_per_tok` a token (every
    pair has a row; the kernels visit the rows that hold one). With
    `remat` a block's forward products run once more in the backward
    pass (2 more FLOPs an entry, a third of 6), all but the last of its
    feed-forward (the dense or shared `down`: no gradient needs its
    output, and XLA drops it; the routed `down`'s output feeds the
    gates' gradient and stays). None of this enters `routed`: it is the
    compiler's count of the program, not the model's work."""
    d, f = dims["hidden_size"], dims["moe_intermediate_size"]
    dense, n_routed, _ = blocks(dims)
    buffered = dims["num_experts_per_tok"] * expert_params(dims)
    visible = _outside_routed_experts(dims) + n_routed * buffered
    if not dims.get("remat"):
        return visible
    again = ((dense + n_routed) * attention_params(dims)
             + dense * 2 * d * dims["intermediate_size"]
             + n_routed * (d * dims["n_routed_experts"]
                           + dims["n_shared_experts"] * 2 * d * f
                           + buffered))
    return visible + again / 3
