"""What a training step of the hybrid delta-rule / latent-attention
decoder (`configs/linear_moe/kimi-linear-48b-a3b.json`) has to compute,
from shapes alone, for the share of the model one chip holds. The rules
are `flops.py`'s and `flops_moe.py`'s: forward + backward = 3 x forward,
2 P a token for a parameter matrix of P entries, causal attention
halved, nothing recomputed, nothing elementwise, the routed experts at
the rows an even routing sends to the experts held here. `dims` are the
model's keyword arguments.

The delta rule's own work is counted as the recurrence states it, per
position and head: the read k^T S, the write k (v - read)^T and the
output S^T q, three products of Dk x Dv, 6 Dk Dv FLOPs forward
(`per_token`). `kda_cost` counts instead what the chunked form that
computes it needs, the numerator of `kda_roofline`.
"""
from __future__ import annotations

from benchmark import flops_moe

# Positions a chunk of the program's delta-rule kernels
# (horovod_tpu/ops/kda.py's CHUNK, copied; tests/benchmarking compares).
CHUNK = 64


def linear(dims: dict) -> dict:
    return dims["linear_attn_config"]


def kinds(dims: dict) -> list:
    """(mixer, feed-forward) of every layer the program holds: "kda"
    where `kda_layers` (1-based) holds the layer, "mla" else; "dense"
    for the first `first_k_dense_replace`, "sparse" after."""
    kda_layers = linear(dims)["kda_layers"]
    return [("kda" if n in kda_layers else "mla",
             "dense" if n <= dims["first_k_dense_replace"] else "sparse")
            for n in range(1, dims["num_hidden_layers"] + 1)]


def kda_params(dims: dict) -> int:
    """The products of one delta-rule attention: q, k, v, the decay's
    and the gate's low-rank pairs, the write strength, the output."""
    d, H, D = (dims["hidden_size"], linear(dims)["num_heads"],
               linear(dims)["head_dim"])
    return 3 * d * H * D + 2 * (d * D + D * H * D) + d * H + H * D * d


def mla_params(dims: dict) -> int:
    """The four products of one latent attention without a query low
    rank."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    nope, rope, dv = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                      dims["v_head_dim"])
    return (d * h * (nope + rope) + d * (dims["kv_lora_rank"] + rope)
            + dims["kv_lora_rank"] * h * (nope + dv) + h * dv * d)


def _mixer_params(dims: dict, mixer: str) -> int:
    return kda_params(dims) if mixer == "kda" else mla_params(dims)


def _outside_routed_experts(dims: dict) -> int:
    """Entries a token is multiplied with outside the routed experts'
    grouped products: the mixers' products, the dense feed-forward,
    routers, shared experts, the head."""
    d = dims["hidden_size"]
    total = d * dims["vocab_size"]
    for mixer, mlp in kinds(dims):
        total += _mixer_params(dims, mixer)
        if mlp == "sparse":
            total += (d * dims["n_routed_experts"] + dims["n_shared_experts"]
                      * flops_moe.expert_params(dims))
        else:
            total += 3 * d * dims["intermediate_size"]
    return total


def _routed_layers(dims: dict) -> int:
    return sum(mlp == "sparse" for _, mlp in kinds(dims))


def per_token(dims: dict, seq: int) -> float:
    """Model FLOPs per token of one training step: `mfu`'s numerator."""
    matmul = _outside_routed_experts(dims) + (
        _routed_layers(dims) * flops_moe.expected_expert_rows_per_token(dims)
        * flops_moe.expert_params(dims))
    mixers = [mixer for mixer, _ in kinds(dims)]
    heads = dims["num_attention_heads"] * (
        dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
        + dims["v_head_dim"])
    attention = 2 * seq * heads * mixers.count("mla") / 2      # causal
    H, D = linear(dims)["num_heads"], linear(dims)["head_dim"]
    recurrence = 6 * H * D * D * mixers.count("kda")
    return 3.0 * (2 * matmul + attention + recurrence)


def kda_cost(dims: dict, seq: int, batch: int, backward: bool,
             itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one delta-rule call over `batch` sequences
    of `seq` positions in chunks of CHUNK, forward or backward, whatever
    implements it.

    FLOPs, per chunk of C and head, as the chunked form's equations
    (ops/kda.py) need them: the key scores A (C (C - 1) / 2 pairs) and
    the query scores P (C (C + 1) / 2 pairs) at 2 Dk a pair; the
    triangular solve (I + A) [U | W] = diag(beta) [V | K^], 2 (Dv + Dk) a
    pair below the diagonal; W S_0, Q^ S_0 and the state's update
    K~^T U~, 2 C Dk Dv each; P U~ at 2 Dv a pair on and below the
    diagonal. The backward pass is twice the forward. Elementwise work
    (the decays, the running sum, the norms and the gate) is not
    counted.

    Bytes are the tensors that must cross HBM once: forward reads q, k,
    v and the output gate (`itemsize`), g and beta (float32) and writes
    o (`itemsize`); backward reads those and dO and writes dq, dk, dv,
    dgate, dg and dbeta."""
    H, D = linear(dims)["num_heads"], linear(dims)["head_dim"]
    C = CHUNK
    below, on_and_below = C * (C - 1) / 2, C * (C + 1) / 2
    per_chunk = (below * 2 * D + on_and_below * 2 * D
                 + below * 2 * (D + D) + 3 * 2 * C * D * D
                 + on_and_below * 2 * D)
    chunks = batch * H * -(-seq // C)
    passes = 2 if backward else 1
    flops_ = passes * per_chunk * chunks
    tokens = batch * seq
    ins = tokens * H * (4 * D * itemsize + D * 4 + 4)
    outs = tokens * H * D * itemsize
    bytes_ = 2 * ins + outs if backward else ins + outs
    return float(flops_), float(bytes_)


def matmul_params(dims: dict) -> float:
    """What the v5e compile test holds XLA's own FLOP count to, as
    entries a token is multiplied with at 6 FLOPs an entry
    (`flops_moe.matmul_params` says what XLA sees: the products outside
    custom calls, the grouped products at the dispatch buffer's rows,
    and with `remat` a block's forward products once more but the last
    of its dense or shared feed-forward). The delta rule's and the
    latent attention's kernels are custom calls XLA does not count."""
    d = dims["hidden_size"]
    buffered = dims["num_experts_per_tok"] * flops_moe.expert_params(dims)
    visible = _outside_routed_experts(dims) + _routed_layers(dims) * buffered
    if not dims.get("remat"):
        return visible
    again = 0
    for mixer, mlp in kinds(dims):
        again += _mixer_params(dims, mixer)
        if mlp == "sparse":
            again += (d * dims["n_routed_experts"] + dims["n_shared_experts"]
                      * 2 * d * dims["moe_intermediate_size"] + buffered)
        else:
            again += 2 * d * dims["intermediate_size"]
    return visible + again / 3
