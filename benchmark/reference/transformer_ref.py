"""Plain reference of the GPT-2 / BERT block stack the benchmark's cells train.

Straightforward `jax.numpy` in float32 at the highest matmul precision
(on a TPU an f32 matmul otherwise runs in bf16 passes): no kernel, no
cache, no sharding, nothing imported from the program under test. It
reads the program's parameter tree (that is what "the same weights"
means) and computes

    x   = embedding[ids] + pos_embedding[:S]
    x   = x + proj(attention(ln1(x)))         per block, pre-LN
    x   = x + wo(gelu(wi(ln2(x))))
    out = head(ln_f(x))

with full multi-head attention over S x S scores, masked below the
diagonal when `dims["causal"]`.

Departures from the published models, all of them the program's
(`models/transformer.py`) and listed in the configuration files under
`assumed`: BERT's block is pre-LN with a final LayerNorm here (the
published one is post-LN), LayerNorm's epsilon is flax's 1e-6 (GPT-2
publishes 1e-5, BERT 1e-12), GELU is the tanh approximation for both
(BERT publishes the erf form), the head is an untied bias-free matrix,
no dropout, no segment embeddings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, causal: bool):
    """x (B, S, d). qkv kernel (d, 3, H, Hd), out kernel (H, Hd, d)."""
    qkv = jnp.einsum("bsd,dthe->bsthe", x, p["qkv"]["kernel"]) \
        + p["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]      # (B, S, H, Hd)
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    if causal:
        s = x.shape[1]
        allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhe->bqhe", probs, v)
    return jnp.einsum("bqhe,hed->bqd", ctx, p["out"]["kernel"]) \
        + p["out"]["bias"]


def _mlp(x, p):
    h = _gelu_tanh(x @ p["wi"]["kernel"] + p["wi"]["bias"])
    return h @ p["wo"]["kernel"] + p["wo"]["bias"]


def embed(params, ids):
    p = params["embed"]
    return p["embedding"][ids] + p["pos_embedding"][None, :ids.shape[1]]


def block(x, p, causal: bool):
    x = x + _attention(_layer_norm(x, p["ln1"]), p["attn"], causal)
    return x + _mlp(_layer_norm(x, p["ln2"]), p["mlp"])


def head(params, x):
    kernel = (params.get("lm_head") or params["mlm_head"])["kernel"]
    return _layer_norm(x, params["ln_f"]) @ kernel


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def forward(params, ids, dims: dict, choices=None):
    """(logits (B, S, V) in float32, None): this stack chooses nothing,
    so `choices` is ignored and there is no `choice_slack` to return.
    Each block is its own jitted call, so only one layer's S x S scores
    are alive at a time."""
    one_block = jax.jit(functools.partial(block, causal=dims["causal"]))
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        x = jax.jit(embed)(params, ids)
        for i in range(dims["n_layers"]):
            x = one_block(x, params["stack"][f"layer_{i}"])
        return jax.jit(head)(params, x), None


def loss(params, ids, dims: dict, choices=None):
    """Mean next-token cross-entropy, the float32 counterpart of the
    objective the cells' trainers differentiate (`lm_objective` in
    trainers/__init__.py), as one differentiable function for the
    gradient comparison. `choices` is ignored."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        x = embed(params, ids)
        for i in range(dims["n_layers"]):
            x = block(x, params["stack"][f"layer_{i}"], dims["causal"])
        logp = jax.nn.log_softmax(head(params, x)[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked)
