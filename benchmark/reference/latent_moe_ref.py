"""Plain reference of the latent-attention mixture-of-experts decoder
(`configs/latent_moe/joyai-llm-flash.json`; DeepSeek-V3's block family with a
multi-token-prediction module), for the share of the model one chip
holds.

Straightforward `jax.numpy` in float32 at the highest matmul precision:
no kernel, no dispatch buffer, no sharding, nothing imported from the
program under test. It reads the program's parameter tree and computes,
with `N(x) = x * rsqrt(mean(x^2) + eps) * scale`:

    attention(x):  c_q = N(x W_qa);  q = c_q W_qb -> H x [nope | rope]
                   [c_kv | k_rope] = x W_kva;  N(c_kv) W_kvb -> H x [k_nope | v]
                   rope on q's rope slice and on the one k_rope all heads share
                   (adjacent pairs turned by p * theta^(-2j/64))
                   softmax(q k^T / sqrt(192) + causal) v, heads joined, W_o
    mlp_f(x):      W_down(silu(x W_gate) * (x W_up))
    routed(x):     s = sigmoid(x W_r) over ALL experts, in float32
                   w = s[chosen] / (sum s[chosen] + 1e-20) * 2.5
                   sum over chosen AND held experts e of w_e mlp_e(x), + shared mlp(x)
    block(x):      h = x + attention(N(x));  h + ffn(N(h)), ffn dense first, routed after
    logits:        head(N(x_L))
    mtp:           x' = W_eh [N(x_L) ; N(Emb(t_{i+1}))], one routed block, N, the same head
    loss:          CE(logits_i, t_{i+1}) + 0.3 CE(mtp_i, t_{i+2}), each a mean

`chosen` is never the reference's own decision where the program sowed
its choices (README.md "Discrete choices"): it computes with exactly
those and returns the largest `correct.choice_slack` over its choosing
layers against its own float32 scores. The experts held here are
`experts_held` from `expert_share * experts_held` on: what the absent
experts would add is left out, as in the program.

Memory: every expert held is applied to every token and weighted by
the token's gate for it, 0 where it was not chosen (one dense product
over the 16 experts a layer, exact whatever the routing), one block a
jitted call and attention by blocks of queries in `forward`
(4096 positions), one block a `jax.checkpoint` in `loss` (its gradient
is taken on 1024 positions beside 8 GB of training state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import correct

MTP_WEIGHT = 0.3
QUERY_BLOCK = 1024


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, positions, theta):
    """x (..., S, H, R): pairs (x[2j], x[2j+1]) turned by
    positions * theta^(-2j/R)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(x.shape)


def _attention(x, p, dims):
    """x (B, S, d). Kernels: q_a (d, r_q), q_b (r_q, H, 192), kv_a
    (d, r_kv + 64), kv_b (r_kv, H, 256), o (H, 128, d)."""
    nope, rank = dims["qk_nope_head_dim"], dims["kv_lora_rank"]
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    s = x.shape[1]
    positions = jnp.arange(s)
    c_q = _norm(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = jnp.einsum("bsr,rhe->bshe", c_q, p["q_b"]["kernel"])
    kv_a = x @ p["kv_a"]["kernel"]
    c_kv = _norm(kv_a[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("bsr,rhe->bshe", c_kv, p["kv_b"]["kernel"])
    k_rope = _rope(kv_a[..., None, rank:], positions, theta)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], positions, theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope, (*kv.shape[:-1], k_rope.shape[-1]))], axis=-1)
    v = kv[..., nope:]
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = positions[start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q[:, start:start + QUERY_BLOCK],
                            k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        if dims["causal"]:
            allowed = positions[None, :] <= rows[:, None]
            scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhe->bqhe",
                              jax.nn.softmax(scores, axis=-1), v))
    ctx = jnp.concatenate(out, axis=1)
    return jnp.einsum("bqhe,hed->bqd", ctx, p["o"]["kernel"])


def _mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _routed(x, p, dims, chosen):
    """(output, choice_slack). `chosen` (B, S, k) int32 or None (then the
    reference chooses: what a test of the uncut layer wants)."""
    k = dims["num_experts_per_tok"]
    held = dims.get("experts_held") or dims["n_routed_experts"]
    first = dims.get("expert_share", 0) * held
    scores = jax.nn.sigmoid(x @ p["router"])                 # (B, S, E)
    if chosen is None:
        chosen, slack = jax.lax.top_k(scores, k)[1], jnp.float32(0)
    else:
        slack = correct.choice_slack(scores, chosen, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if dims.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    weights = picked * dims["routed_scaling_factor"]
    # Every expert held is applied to every token; a token's gate for an
    # expert it did not choose is 0.
    mine = first + jnp.arange(held)
    gate = jnp.sum(jnp.where(chosen[..., None] == mine, weights[..., None],
                             0.0), axis=-2)                      # (B, S, held)
    h = jnp.einsum("bsd,edcf->bsecf", x, p["gate_up"])
    act = jax.nn.silu(h[..., 0, :]) * h[..., 1, :] * gate[..., None]
    out = jnp.einsum("bsef,efd->bsd", act, p["down"])
    if "shared" in p:
        shared = p["shared"]
        out = out + _mlp(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                         shared["down"]["kernel"])
    return out, slack


def _block(x, p, chosen, dims):
    eps = dims["rms_norm_eps"]
    h = x + _attention(_norm(x, p["attn_norm"]["scale"], eps), p["attn"],
                       dims)
    normed = _norm(h, p["ffn_norm"]["scale"], eps)
    if "moe" in p:
        out, slack = _routed(normed, p["moe"], dims, chosen)
    else:
        mlp = p["mlp"]
        out, slack = _mlp(normed, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                          mlp["down"]["kernel"]), jnp.float32(0)
    return h + out, slack


def _mtp_input(x, next_embedding, p, dims):
    eps = dims["rms_norm_eps"]
    joined = jnp.concatenate(
        [_norm(x, p["norm_h"]["scale"], eps),
         _norm(next_embedding, p["norm_e"]["scale"], eps)], axis=-1)
    return joined @ p["proj"]["kernel"]


def _head(x, scale, params, dims):
    return _norm(x, scale, dims["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _chosen(choices, *path):
    """The choices the program sowed for one layer, or None."""
    for key in path:
        if not isinstance(choices, dict) or key not in choices:
            return None
        choices = choices[key]
    return choices["moe"]["routed"][0]


def _both_logits(params, ids, dims, choices, wrap):
    """(logits, mtp logits or None, largest slack). `wrap` makes one
    block's function (a jit for `forward`, a checkpoint for `loss`)."""
    block = wrap(functools.partial(_block, dims=dims))
    table = params["embed"]["embedding"]
    x, slacks = table[ids], []
    for i in range(dims["num_hidden_layers"]):
        x, slack = block(x, params[f"layer_{i}"],
                         _chosen(choices, f"layer_{i}"))
        slacks.append(slack)
    logits = _head(x, params["final_norm"]["scale"], params, dims)
    mtp_logits = None
    if dims["num_nextn_predict_layers"]:
        p = params["mtp"]
        y = _mtp_input(x, table[jnp.roll(ids, -1, axis=1)], p, dims)
        y, slack = block(y, p["block"], _chosen(choices, "mtp", "block"))
        slacks.append(slack)
        mtp_logits = _head(y, p["final_norm"]["scale"], params, dims)
    return logits, mtp_logits, jnp.max(jnp.stack(slacks))


def forward(params, ids, dims: dict, choices=None):
    """(logits (B, S, V) in float32, choice_slack): the largest slack
    over the routed layers and the module's (None where no choices were
    given: the reference then chooses for itself)."""
    with jax.default_matmul_precision("highest"):
        logits, _, slack = _both_logits(_f32(params), ids, dims, choices,
                                        jax.jit)
    return logits, (slack if jax.tree.leaves(choices) else None)


def _xent(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, ids, dims: dict, choices=None, mtp_weight=MTP_WEIGHT):
    """The float32 counterpart of trainers/gspmd_mtp.py's objective:
    next-token cross-entropy plus `mtp_weight` x the module's, whose
    position i predicts token i + 2."""
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits, _ = _both_logits(_f32(params), ids, dims,
                                             choices, jax.checkpoint)
        value = _xent(logits[:, :-1], ids[:, 1:])
        if mtp_logits is not None:
            value = value + mtp_weight * _xent(mtp_logits[:, :-2],
                                               ids[:, 2:])
        return value
