"""Plain reference of the hybrid decoder whose layers mix tokens with
Kimi Delta Attention or with latent attention without positions, each
with a dense or a routed feed-forward
(`configs/linear_moe/kimi-linear-48b-a3b.json`), for the share of the
model one chip holds.

Straightforward `jax.numpy` in float32 at the highest matmul precision:
no kernel, no chunks, no dispatch buffer, no sharding, nothing imported
from the program under test. It reads the program's parameter tree and
computes, with `N(x) = x * rsqrt(mean(x^2) + eps) * scale`,
`L2(x) = x * rsqrt(sum(x^2) + 1e-6)` over each head's channels and
`conv(y)_t = sum_i w_i * y_(t - 3 + i)` (causal, depthwise, width 4):

    kda(x):     q = L2(silu(conv(x W_q))), k = L2(silu(conv(x W_k))),
                v = silu(conv(x W_v)), heads of 128 (`linear_attn_config`)
                g = -exp(A_log[h]) softplus(x W_fa W_fb + dt_bias)
                beta = sigmoid(x W_b)
                S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T,
                S_0 = 0, one position at a time
                o_t = S_t^T q_t / sqrt(128)
                o <- N_head(o) * sigmoid(x W_ga W_gb);  concat(o) W_o
    mla(x):     q = x W_q -> H x [nope | rope];  [c_kv | k_rope] = x W_kva
                N(c_kv) W_kvb -> H x [k_nope | v];  k = [k_nope | k_rope], k_rope
                shared by all heads; no rotary
                softmax(q k^T / sqrt(192) + causal) v, heads joined, W_o
    mlp_f(x):   W_down(silu(x W_gate) * (x W_up))
    routed(x):  `latent_moe_ref._routed`: s = sigmoid(x W_r) over ALL experts,
                w = s[chosen] / (sum s[chosen] + 1e-20) * 2.446, the sum over chosen
                AND held experts of w_e mlp_e(x), + the shared mlp(x)
    block_l(x): h = x + mixer_l(N(x));  h + ffn_l(N(h)); layer l + 1 is kda where
                `kda_layers` holds it, mla where `full_attn_layers` does; ffn dense for
                the first `first_k_dense_replace` layers, routed after
    logits:     head(N(x_L));  loss: CE(logits_i, t_{i+1}), a mean

`chosen` is never the reference's own decision where the program sowed
its choices (README.md "Discrete choices"); the largest
`correct.choice_slack` over the routed layers comes back beside the
logits. The routed layer, the norm, the gated feed-forward and the
cross-entropy are `latent_moe_ref`'s own functions.

Memory: one block a jitted call and attention by blocks of
QUERY_BLOCK queries in `forward`; one block a `jax.checkpoint` in
`loss`, and the recurrence a `lax.scan` over blocks of SCAN_BLOCK
positions, each block a `jax.checkpoint` of a scan over its positions,
so that its gradient keeps one state a block, not one a position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_ref import (
    _f32, _mlp, _norm, _routed, _xent)

QUERY_BLOCK = 512
SCAN_BLOCK = 64
L2_EPS = 1e-6


def _conv(y, w):
    """y (B, S, C), w (K, C): causal depthwise convolution, no bias."""
    width, s = w.shape[0], y.shape[1]
    padded = jnp.pad(y, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * w[i] for i in range(width))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule one position at a time. q, k, g (B, S, H, Dk),
    v (B, S, H, Dv), beta (B, S, H) -> o (B, S, H, Dv)."""
    B, S, H, Dk = q.shape
    pad = -S % SCAN_BLOCK

    def position(state, inputs):
        qt, kt, vt, gt, bt = inputs                       # (B, H, ...)
        state = jnp.exp(gt)[..., None] * state            # (B, H, Dk, Dv)
        read = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + bt[..., None, None] * kt[..., :, None] * (
            vt - read)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt) / jnp.sqrt(
            jnp.float32(Dk))

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(position, state, inputs)

    def blocked(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(-1, SCAN_BLOCK, *x.shape[1:])

    state = jnp.zeros((B, H, Dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, state, tuple(
        blocked(x) for x in (q, k, v, g, beta)))
    o = o.reshape(-1, *o.shape[2:])[:S]
    return jnp.moveaxis(o, 0, 1)


def _kda(x, p, dims):
    """x (B, S, d). Kernels: q, k, v (d, H D); f_a, g_a (d, D); f_b, g_b
    (D, H D); b (d, H); o (H D, d); q_conv, k_conv, v_conv (4, H D);
    A_log (H,); dt_bias (H D,); o_norm (D,)."""
    linear = dims["linear_attn_config"]
    H, D = linear["num_heads"], linear["head_dim"]
    B, s, _ = x.shape

    def mixed(name):
        y = _conv(x @ p[name]["kernel"], p[f"{name}_conv"]["kernel"])
        return jax.nn.silu(y).reshape(B, s, H, D)

    q, k, v = _l2(mixed("q")), _l2(mixed("k")), mixed("v")
    f = x @ p["f_a"]["kernel"] @ p["f_b"]["kernel"] + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f.reshape(B, s, H, D))
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
    o = _norm(delta_rule(q, k, v, g, beta), p["o_norm"], dims["rms_norm_eps"])
    gate = jax.nn.sigmoid(x @ p["g_a"]["kernel"] @ p["g_b"]["kernel"])
    return (o.reshape(B, s, H * D) * gate) @ p["o"]["kernel"]


def _mla(x, p, dims):
    """x (B, S, d). Kernels: q (d, H, 192), kv_a (d, r_kv + 64), kv_b
    (r_kv, H, 256), o (H, 128, d)."""
    nope, rank = dims["qk_nope_head_dim"], dims["kv_lora_rank"]
    s = x.shape[1]
    positions = jnp.arange(s)
    q = jnp.einsum("bsd,dhe->bshe", x, p["q"]["kernel"])
    kv_a = x @ p["kv_a"]["kernel"]
    c_kv = _norm(kv_a[..., :rank], p["kv_a_norm"]["scale"],
                 dims["rms_norm_eps"])
    kv = jnp.einsum("bsr,rhe->bshe", c_kv, p["kv_b"]["kernel"])
    k_rope = kv_a[..., None, rank:]
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (*kv.shape[:-1], k_rope.shape[-1]))], axis=-1)
    v = kv[..., nope:]
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = positions[start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q[:, start:start + QUERY_BLOCK],
                            k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        allowed = positions[None, :] <= rows[:, None]
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhe->bqhe",
                              jax.nn.softmax(scores, axis=-1), v))
    ctx = jnp.concatenate(out, axis=1)
    return jnp.einsum("bqhe,hed->bqd", ctx, p["o"]["kernel"])


def _block(x, p, chosen, dims, kda: bool):
    eps = dims["rms_norm_eps"]
    mixer = _kda if kda else _mla
    h = x + mixer(_norm(x, p["attn_norm"]["scale"], eps), p["attn"], dims)
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
    kda_layers = dims["linear_attn_config"]["kda_layers"]
    block = {kind: wrap(functools.partial(_block, dims=dims, kda=kind))
             for kind in (True, False)}
    for i in range(dims["num_hidden_layers"]):
        x, slack = block[i + 1 in kda_layers](
            x, params[f"layer_{i}"], _chosen(choices, f"layer_{i}"))
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
