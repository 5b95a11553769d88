"""What a training step has to compute, from shapes alone.

These functions are the numerator of `mfu` and of the kernels' roofline
shares. They count the operations the mathematics needs — never what a
compiler or a kernel happens to execute — so a change to the program
cannot move them:

* forward + backward = 3 x forward (each matmul has two gradient
  matmuls of its own size);
* a matmul with a parameter matrix of P entries costs 2 P per token;
* attention scores and the weighted sum cost 2 S d_model each per token
  and layer at sequence length S, i.e. 4 S d_model, HALVED when the
  mask is causal (the upper triangle is not part of the model);
* nothing recomputed (a flash backward's second pass over the scores,
  rematerialised activations) and nothing elementwise (LayerNorm, GELU,
  softmax, the optimizer) is counted.

A configuration names its function in its file (`"flops_per_token"`);
one that is not a plain transformer brings its own as a new file beside
this one (see README.md).
"""
from __future__ import annotations


def transformer_matmul_params(dims: dict) -> int:
    """Entries of the parameter matrices a token is multiplied with: per
    block the fused qkv (3 d^2), the attention output (d^2) and the two
    feed-forward matrices (2 d d_ff); once, the head (d V). Embedding
    and position lookups are gathers, not matmuls."""
    d, f = dims["d_model"], dims["d_ff"]
    per_block = 4 * d * d + 2 * d * f
    return dims["n_layers"] * per_block + d * dims["vocab_size"]


def transformer(dims: dict, seq: int) -> float:
    """Model FLOPs per token of one training step of the GPT-2 / BERT
    block stack (`TransformerLM`, `TransformerEncoder`)."""
    matmul = 2 * transformer_matmul_params(dims)
    attention = 4 * seq * dims["d_model"] * dims["n_layers"]
    if dims["causal"]:
        attention //= 2
    return 3.0 * (matmul + attention)


def attention_kernel_cost(batch: int, seq: int, heads: int, head_dim: int,
                          causal: bool, backward: bool,
                          itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a fused attention kernel needs.

    Forward: S = Q K^T and O = P V, 2 S^2 D each per (batch, head).
    Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q — four
    matmuls of that size. The score recomputation a flash backward does
    is the kernel's choice, not the model's, and is not counted (the
    same rule as for `mfu`). Causal halves both.

    Bytes are the tensors that must cross HBM once: forward reads Q, K,
    V and writes O and the per-row logsumexp (f32); backward reads Q, K,
    V, O, dO and the logsumexp and writes dQ, dK, dV.
    """
    tensor = batch * seq * heads * head_dim * itemsize
    rows = batch * heads * seq * 4
    matmuls, tensors = (4, 8) if backward else (2, 4)
    flops = matmuls * 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        flops /= 2
    return flops, float(tensors * tensor + rows)


def least_seconds(flops: float, bytes_: float, peaks) -> tuple[float, str]:
    """The least time the chip could take for that work, and which of
    its two peaks sets it."""
    compute = flops / peaks.bf16_flops_per_s
    memory = bytes_ / peaks.hbm_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")
