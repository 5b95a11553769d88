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


def attention_kernel_cost(batch: int, seq: int, heads: int, qk_head_dim: int,
                          v_head_dim: int, causal: bool, backward: bool,
                          itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of a fused attention kernel needs, for
    heads whose queries and keys are `qk_head_dim` wide and whose values
    are `v_head_dim` wide (the same number in GPT-2 and BERT; a latent
    attention's heads differ).

    Forward: S = Q K^T, 2 S^2 Dqk, and O = P V, 2 S^2 Dv, per (batch,
    head). Backward: dQ = dS K and dK = dS^T Q at Dqk, dV = P^T dO and
    dP = dO V^T at Dv: twice the forward. The score recomputation a
    flash backward does is the kernel's choice, not the model's, and is
    not counted (the same rule as for `mfu`). Causal halves both.

    Bytes are the tensors that must cross HBM once: forward reads Q, K
    (Dqk) and V (Dv) and writes O (Dv) and the per-row logsumexp (f32);
    backward reads Q, K, V, O, dO and the logsumexp and writes dQ, dK,
    dV: four tensors of each width.
    """
    per_dim = batch * seq * heads * itemsize
    rows = batch * heads * seq * 4
    passes = 2 if backward else 1
    flops = passes * 2.0 * batch * heads * seq * seq * (qk_head_dim
                                                        + v_head_dim)
    if causal:
        flops /= 2
    tensors = 2 * passes * per_dim * (qk_head_dim + v_head_dim)
    return flops, float(tensors + rows)


def least_seconds(flops: float, bytes_: float, peaks) -> tuple[float, str]:
    """The least time the chip could take for that work, and which of
    its two peaks sets it."""
    compute = flops / peaks.bf16_flops_per_s
    memory = bytes_ / peaks.hbm_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")
