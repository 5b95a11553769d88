"""Plain reference of the grouped-query decoder with sliding-window and
full layers, a per-head output gate and a routed feed-forward
(`configs/window_moe/laguna-xs2.json`), for the share of the model one
chip holds.

Straightforward `jax.numpy` in float32 at the highest matmul precision:
no kernel, no dispatch buffer, no sharding, nothing imported from the
program under test. It reads the program's parameter tree and computes,
with `N(x) = x * rsqrt(mean(x^2) + eps) * scale` and layer l of kind
`layer_types[l]` with `H_l` query heads (read off `W_q`'s shape) over
`H_kv` key/value heads of `head_dim` D:

    attention_l(x): q = x W_q -> (S, H_l, D);  k = x W_k, v = x W_v -> (S, H_kv, D)
                    rotary on the first rot = partial_rotary_factor * D dims of q and k,
                    rotate-half: (x[j], x[j + rot/2]) turned by p * f_j, cos and sin
                    times attention_factor; f_j plain (theta^(-2j/rot)) on sliding
                    layers, YaRN on full ones (`_yarn_frequencies`)
                    k, v repeated H_l / H_kv times: head h attends k, v of h // (H_l / H_kv)
                    scores q k^T / sqrt(D), key j visible to query i iff j <= i, and on
                    sliding layers i - j < sliding_window (a mask over all S keys)
                    o_h = softmax(scores) v;  o_h <- sigmoid(x W_g)_h * o_h;  concat(o) W_o
    mlp_f(x):       W_down(silu(x W_gate) * (x W_up))
    routed(x):      `latent_moe_ref._routed`: s = sigmoid(x W_r) over ALL experts,
                    w = s[chosen] / (sum s[chosen] + 1e-20) * 2.5, the sum over chosen
                    AND held experts of w_e mlp_e(x), + the shared mlp(x)
    block_l(x):     h = x + attention_l(N(x));  h + ffn_l(N(h)), ffn dense where
                    `mlp_layer_types[l]` says so, routed else
    logits:         head(N(x_L));  loss: CE(logits_i, t_{i+1}), a mean

`chosen` is never the reference's own decision where the program sowed
its choices (README.md "Discrete choices"); the largest
`correct.choice_slack` over the routed layers comes back beside the
logits. The routed layer, the norm, the gated feed-forward and the
cross-entropy are `latent_moe_ref`'s own functions: the same equations,
kept once.

Memory: one block a jitted call and attention by blocks of QUERY_BLOCK
queries in `forward` (at 8192 positions and 64 heads a block's float32
scores are 1.07 GB), one block a `jax.checkpoint` in `loss` (its
gradient is taken on 1024 positions beside 6 GB of training state).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.latent_moe_ref import (
    _f32, _mlp, _norm, _routed, _xent)

QUERY_BLOCK = 512
SLIDING = "sliding_attention"


def _yarn_frequencies(rot: int, rope: dict) -> np.ndarray:
    """YaRN (Peng et al. 2023, as Hugging Face's
    `_compute_yarn_parameters` reads the five keys): pair j's plain
    frequency is theta^(-2j/rot); the pair index at which a wave makes
    `n` turns within the original context is
    rot ln(L / (2 pi n)) / (2 ln theta). Pairs below that index for
    `beta_fast` keep the plain frequency, pairs above that for
    `beta_slow` get frequency / factor, and between the two the share of
    the divided one rises linearly with the pair index."""
    theta, length = rope["rope_theta"], rope["original_max_position_embeddings"]

    def pair_with_turns(n):
        return rot * math.log(length / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_with_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_with_turns(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    divided = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - divided) + plain / rope["factor"] * divided


def _rotary(x, positions, rope: dict):
    """x (B, S, H, D): the first rot dims turned, rotate-half."""
    rot = int(x.shape[-1] * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "yarn":
        freq = _yarn_frequencies(rot, rope)
    else:
        freq = rope["rope_theta"] ** (
            -np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32)[None, :])
    factor = jnp.float32(rope.get("attention_factor", 1.0))
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _attention(x, p, dims, kind: str):
    """x (B, S, d). Kernels: q (d, H_l, D), k and v (d, H_kv, D), gate
    (d, H_l), o (H_l, D, d)."""
    s = x.shape[1]
    positions = jnp.arange(s)
    rope = dims["rope_parameters"][kind]
    q = _rotary(jnp.einsum("bsd,dhe->bshe", x, p["q"]["kernel"]), positions,
                rope)
    k = _rotary(jnp.einsum("bsd,dhe->bshe", x, p["k"]["kernel"]), positions,
                rope)
    v = jnp.einsum("bsd,dhe->bshe", x, p["v"]["kernel"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = positions[start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q[:, start:start + QUERY_BLOCK],
                            k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        behind = rows[:, None] - positions[None, :]          # i - j
        allowed = behind >= 0
        if kind == SLIDING:
            allowed = allowed & (behind < dims["sliding_window"])
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhe->bqhe",
                              jax.nn.softmax(scores, axis=-1), v))
    ctx = jnp.concatenate(out, axis=1)
    ctx = ctx * jax.nn.sigmoid(x @ p["gate"]["kernel"])[..., None]
    return jnp.einsum("bqhe,hed->bqd", ctx, p["o"]["kernel"])


def _block(x, p, chosen, dims, kind: str):
    eps = dims["rms_norm_eps"]
    h = x + _attention(_norm(x, p["attn_norm"]["scale"], eps), p["attn"],
                       dims, kind)
    normed = _norm(h, p["ffn_norm"]["scale"], eps)
    if "moe" in p:
        out, slack = _routed(normed, p["moe"], dims, chosen)
    else:
        mlp = p["mlp"]
        out, slack = _mlp(normed, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                          mlp["down"]["kernel"]), jnp.float32(0)
    return h + out, slack


def _chosen(choices, layer: str):
    """The choices the program sowed for one layer, or None."""
    if not isinstance(choices, dict) or layer not in choices:
        return None
    return choices[layer]["moe"]["routed"][0]


def _logits(params, ids, dims, choices, wrap):
    """(logits, largest slack). `wrap` makes one block's function (a jit
    for `forward`, a checkpoint for `loss`)."""
    x, slacks = params["embed"]["embedding"][ids], []
    kinds = dims["layer_types"][:dims["num_hidden_layers"]]
    block = {kind: wrap(functools.partial(_block, dims=dims, kind=kind))
             for kind in set(kinds)}
    for i, kind in enumerate(kinds):
        x, slack = block[kind](x, params[f"layer_{i}"],
                               _chosen(choices, f"layer_{i}"))
        slacks.append(slack)
    x = _norm(x, params["final_norm"]["scale"], dims["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], jnp.max(jnp.stack(slacks))


def forward(params, ids, dims: dict, choices=None):
    """(logits (B, S, V) in float32, choice_slack): the largest slack
    over the routed layers (None where no choices were given: the
    reference then chooses for itself)."""
    with jax.default_matmul_precision("highest"):
        logits, slack = _logits(_f32(params), ids, dims, choices, jax.jit)
    return logits, (slack if jax.tree.leaves(choices) else None)


def loss(params, ids, dims: dict, choices=None):
    """The float32 counterpart of `lm_loss`: next-token cross-entropy,
    a mean over the positions that have a next token."""
    with jax.default_matmul_precision("highest"):
        logits, _ = _logits(_f32(params), ids, dims, choices, jax.checkpoint)
        return _xent(logits[:, :-1], ids[:, 1:])
