"""Latent-attention mixture-of-experts decoder: the DeepSeek-V3 block
family, with JoyAI-LLM-Flash (48B-A2.7B) as the registry's entry.

What differs from `models/transformer.py`'s GPT-2 / BERT block, by
mechanism (field names are the published `config.json` keys):

* RMSNorm, no biases, a gated (SwiGLU) feed-forward, an untied head;
* **latent attention** (`LatentAttention`): queries through a low-rank
  path `x W_qa -> RMSNorm -> W_qb` (`x W_q` where `q_lora_rank` is None),
  keys and values through a shared compressed vector
  `x W_kva -> [c_kv | k_rope]`, `RMSNorm(c_kv) W_kvb`. A head's query /
  key is `[nope | rope]` (128 + 64 = 192 wide), its value 128: the
  attention kernel gets heads of two sizes (`ops/flash_attention.py`).
  Rotary positions, where given, turn adjacent pairs of the 64-wide rope
  slice as complex numbers (`rope_interleave`); one `k_rope` a position
  is shared by all heads. Training materialises `k` and `v` per head; the
  absorbed form that attends over the latent cache is a serving matter;
* **a routed layer told which experts it holds** (`RoutedExperts`): a
  float32 sigmoid router over ALL `n_routed_experts`, top
  `num_experts_per_tok`, gates normalised over the chosen and scaled
  by `routed_scaling_factor`; of the chosen experts the layer computes
  those it holds — `experts_held` of them, share `expert_share` of
  `n_routed_experts // experts_held` — plus the shared expert, which
  every share computes alike. That is what one expert-parallel shard
  runs between its two exchanges; on one chip it runs without them, and
  nothing stands in for the absent experts: their part of the sum is
  left out. **No token is dropped under any routing**: the dispatch
  buffer has a row for every (token, expert) pair, the chosen-and-held
  pairs sorted to its front by expert; `ops/routed_rows.py` moves and
  `ops/grouped_matmul.py` multiplies the rows that hold one: the work
  follows the rows present and nothing branches on the device
  (`held_experts`);
* a per-layer pattern: `first_k_dense_replace` leading dense layers,
  routed layers after;
* **a multi-token-prediction module** (`MTPModule`, DeepSeek-V3 report
  section 2.2): `W_eh [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]`, one
  routed block, a final norm, the model's own embedding and head,
  predicting `t_{i+2}`. The model hands back what the second loss term
  is computed from — the module's logits, sown into the trainer's
  `AUX_COLLECTION` — not a reduced scalar: `make_train_step(...,
  aux_loss_fn=mtp_loss(...))` adds the term. The module runs only when
  that collection is mutable: `model.apply(variables, ids)` is the main
  model alone.

`e_score_correction_bias` (`topk_method` "noaux_tc") is held at its
initial value 0 as a constant: its update is a training procedure the
published config does not give, and it is no parameter (it has no
gradient). One expert group only (`n_group` = `topk_group` = 1).

Discrete choices are sown into the collection `choices` as
(batch, seq, k) int32 (benchmark/README.md "Discrete choices").
Regions of the XLA profile: `hvd.attn.latent`, `hvd.moe.route`,
`hvd.moe.experts`, `hvd.mtp`, `hvd.mlp`, `hvd.norm`, `hvd.embed`
(docs/tracing.md "Under jit").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..common import telemetry, tracing
from ..ops import routed_rows
from ..ops.grouped_matmul import grouped_matmul
from ..parallel.train import AUX_COLLECTION
from .transformer import _attention_dispatch, _dense, default_kernel_init

Dtype = Any

# The flax collection of the model's discrete choices (a benchmark
# contract); the multi-token-prediction module's logits go into the
# trainer's `AUX_COLLECTION`.
CHOICES_COLLECTION = "choices"
# `checkpoint_name` of a routed layer's chosen experts.
CHOSEN_EXPERTS = "moe_chosen_experts"

_BUFFER_HELP = ("Routed-expert layer as built: experts held here, the "
                "shares the experts are divided into, rows of the "
                "dispatch buffer (kind: expected, buffer) and the rows one "
                "program of its kernels moves (kind: tile)")


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """Hyperparameters under their published `config.json` names;
    defaults are JoyAI-LLM-Flash's."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    intermediate_size: int = 7168
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    # The share of a layer's routed experts this program holds: share
    # `expert_share` of `n_routed_experts // experts_held` equal ones
    # (experts `expert_share * experts_held` onward). None: all of them.
    experts_held: Optional[int] = None
    expert_share: int = 0
    # Engineering knobs, as `TransformerConfig`'s.
    causal: bool = True
    attn_impl: str = "dense"      # or "flash" (ops/flash_attention.py)
    remat: bool = False           # recompute each block in the backward pass
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    logits_dtype: Dtype = jnp.float32

    def __post_init__(self):
        held = self.held
        if self.n_routed_experts % held or not (
                0 <= self.expert_share < self.n_routed_experts // held):
            raise ValueError(
                f"experts_held={held} must divide n_routed_experts="
                f"{self.n_routed_experts}, and expert_share="
                f"{self.expert_share} must name one of the shares")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl {self.attn_impl!r}: dense or flash")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# ---------------------------------------------------------------- pieces

class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * scale, statistics in float32."""

    cfg: LatentMoEConfig
    axis: str = "embed"

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(),
                                         (self.axis,)),
            (x.shape[-1],), self.cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True)
            + self.cfg.rms_norm_eps)
        return (y * scale).astype(self.cfg.dtype)


def rope_angles(positions, dim: int, theta: float):
    """(cos, sin), each (S, dim // 2) float32: pair j of a position p
    turns by p * theta^(-2j / dim)."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_interleaved(x, cos, sin):
    """Rotate adjacent pairs (x[2j], x[2j+1]) of x's last axis by the
    angles of `rope_angles` (None: no positions). The published code
    de-interleaves q and k alike first: the same scores."""
    if cos is None:
        return x
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention, training form (module docstring);
    `rope` None leaves the rope slices as they are (no positions)."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, rope):
        cfg, rope = self.cfg, rope or (None, None)
        B, S, _ = x.shape
        H = cfg.num_attention_heads
        nope, rot, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)

        def heads(features, name, in_axis):
            return nn.DenseGeneral(
                (H, features), axis=-1, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, (in_axis, "heads", "kv")),
                name=name)

        with jax.named_scope(tracing.SCOPE_ATTN_LATENT):
            q = _latent_query(cfg, x, heads(nope + rot, "q_b", "latent"),
                              heads(nope + rot, "q", "embed"))  # (B,S,H,192)
            kv_a = _dense(cfg.kv_lora_rank + rot, cfg, "kv_a",
                          ("embed", "latent"), use_bias=False)(x)
            c_kv = RMSNorm(cfg, "latent", name="kv_a_norm")(
                kv_a[..., :cfg.kv_lora_rank])
            k_rope = kv_a[..., None, cfg.kv_lora_rank:]      # (B,S,1,64)
            kv = heads(nope + dv, "kv_b", "latent")(c_kv)    # (B,S,H,256)
            q = jnp.concatenate(
                [q[..., :nope], apply_rope_interleaved(q[..., nope:], *rope)],
                axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(apply_rope_interleaved(k_rope, *rope),
                                  (B, S, H, rot))], axis=-1)
            v = kv[..., nope:]
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
        ctx = _attention_dispatch(cfg, q, k, v, None)        # (B,S,H,128)
        with jax.named_scope(tracing.SCOPE_ATTN_LATENT):
            out = nn.DenseGeneral(
                cfg.hidden_size, axis=(-2, -1), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, ("heads", "kv", "embed")),
                name="o")(ctx)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class GatedMLP(nn.Module):
    """W_down(silu(x W_gate) * (x W_up))."""

    cfg: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(self.width, cfg, "gate", ("embed", "mlp"),
                      use_bias=False)(x)
        up = _dense(self.width, cfg, "up", ("embed", "mlp"),
                    use_bias=False)(x)
        h = nn.with_logical_constraint(nn.silu(gate) * up,
                                       ("batch", "seq", "mlp"))
        return _dense(cfg.hidden_size, cfg, "down", ("mlp", "embed"),
                      use_bias=False)(h)


def buffer_rows(tokens: int, cfg: LatentMoEConfig) -> tuple:
    """(expected, buffer) rows of a routed layer's dispatch buffer for
    `tokens` tokens: an even routing sends `k * held / total` rows a
    token here; the buffer holds the worst case, every token choosing
    only experts held here."""
    worst = tokens * cfg.num_experts_per_tok
    return worst * cfg.held // cfg.n_routed_experts, worst


def held_experts(tokens, gates, w_in, w_out, chosen, first: int, held: int):
    """sum over the chosen-and-held (token, expert) pairs of gate x
    Expert(token), (T, D) float32, for ANY routing. tokens (T, D), gates
    and chosen (T, k), w_in (held, D, 2 F) gate and up side by side,
    w_out (held, F, D); the experts held are `first` to `first + held`.

    Every shape is static and nothing branches on the device: the
    dispatch buffer has a row for every pair, T k (every token may choose
    only experts held here), the pairs held sorted to its front by
    expert. Everything that touches the rows follows the rows that hold
    a pair, a count known on the device: the grouped products
    (ops/grouped_matmul.py), the gather into the buffer, the activation
    between the products and the weighted sum back
    (ops/routed_rows.py); the rows behind are never written and never
    read as numbers. What still scales with the buffer: its allocation,
    the sort into expert order (one more in the backward pass, of the
    gates' gradients) and integer vectors of its length."""
    T, k = chosen.shape
    with jax.named_scope(tracing.SCOPE_MOE_ROUTE):
        local = chosen.reshape(T * k) - first
        routing = routed_rows.route(
            jnp.where((local >= 0) & (local < held), local, held), gates,
            held)
        buffer = routed_rows.dispatch(tokens, routing)
    with jax.named_scope(tracing.SCOPE_MOE_EXPERTS):
        h = grouped_matmul(buffer, w_in, routing.sizes)
        h = routed_rows.gated_activation(h, routing.held_rows)
        y = grouped_matmul(h, w_out, routing.sizes)
    with jax.named_scope(tracing.SCOPE_MOE_ROUTE):
        return routed_rows.combine(y, gates, routing)


class RoutedExperts(nn.Module):
    """The routed feed-forward of one layer, for the experts held here,
    plus the shared expert (module docstring)."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, D = x.shape
        T, k, held = B * S, cfg.num_experts_per_tok, cfg.held
        total, f = cfg.n_routed_experts, cfg.moe_intermediate_size
        first = cfg.expert_share * held
        labels = {"experts_held": str(held), "shares": str(total // held)}
        for kind, n in zip(("expected", "buffer", "tile"),
                           (*buffer_rows(T, cfg), routed_rows.ROW_TILE)):
            telemetry.gauge("horovod_moe_dispatch_rows", _BUFFER_HELP,
                            {**labels, "kind": kind}).set(n)

        router = self.param(
            "router",
            nn.with_logical_partitioning(default_kernel_init,
                                         ("embed", None)),
            (D, total), jnp.float32)
        gate_up = self.param(
            "gate_up",
            nn.with_logical_partitioning(
                default_kernel_init, ("expert", "embed", None, "expert_mlp")),
            (held, D, 2, f), cfg.param_dtype)
        down = self.param(
            "down",
            nn.with_logical_partitioning(
                default_kernel_init, ("expert", "expert_mlp", "embed")),
            (held, f, D), cfg.param_dtype)
        tokens = x.reshape(T, D)

        with jax.named_scope(tracing.SCOPE_MOE_ROUTE):
            # Float32 throughout, as published: at the MXU's default an
            # f32 product is one bf16 pass.
            scores = jax.nn.sigmoid(jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            # Selection scores are `scores + e_score_correction_bias`;
            # the bias is the constant 0 (module docstring).
            _, chosen = jax.lax.top_k(scores, k)                  # (T, k)
            # Kept from the forward pass where the block is recomputed
            # (`_block`): a recomputed score that rounds otherwise would
            # flip a choice near a tie, and the backward pass would
            # differentiate another selection than the forward made.
            chosen = checkpoint_name(chosen, CHOSEN_EXPERTS)
            self.sow(CHOICES_COLLECTION, "routed",
                     chosen.reshape(B, S, k).astype(jnp.int32))
            # (Not `top_k`'s own values: where the block is recomputed
            # the gates are the scores of the choices that were kept.
            # Picked by comparison: a gather of T k scalars costs this
            # chip more than the router's product.)
            gates = jnp.sum(
                jnp.where(chosen[:, :, None] == jnp.arange(total),
                          scores[:, None, :], 0), axis=-1)
            if cfg.norm_topk_prob:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + 1e-20)
            gates = gates * cfg.routed_scaling_factor
        routed = held_experts(
            tokens, gates, gate_up.reshape(held, D, 2 * f).astype(cfg.dtype),
            down.astype(cfg.dtype), chosen, first, held)
        out = routed.astype(cfg.dtype).reshape(B, S, D)
        if cfg.n_shared_experts:
            with jax.named_scope(tracing.SCOPE_MOE_EXPERTS):
                out = out + GatedMLP(cfg, cfg.n_shared_experts * f,
                                     name="shared")(x)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class Block(nn.Module):
    """h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h)), the FFN
    dense or routed."""

    cfg: LatentMoEConfig
    routed: bool

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.cfg
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="attn_norm")(x)
        h = x + LatentAttention(cfg, name="attn")(y, rope)
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="ffn_norm")(h)
        if self.routed:
            out = h + RoutedExperts(cfg, name="moe")(y)
        else:
            with jax.named_scope(tracing.SCOPE_MLP):
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(y)
            out = h + y
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


# One object for every block: jax caches what recomputation makes of a
# jitted call inside a block (ops/routed_rows.py's, jnp's own) by the
# policy's identity, so a policy built per block gives each block its
# own copy of every such call to lower.
_KEEP_CHOICES = jax.checkpoint_policies.save_only_these_names(CHOSEN_EXPERTS)


def _block(cfg: LatentMoEConfig):
    """`Block`, recomputed in the backward pass where `cfg.remat`: all of
    it but the routed layer's discrete choices."""
    if not cfg.remat:
        return Block
    return nn.remat(Block, prevent_cse=True, policy=_KEEP_CHOICES)


class TokenEmbedding(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, ids):
        cfg = self.cfg
        table = self.param(
            "embedding",
            nn.with_logical_partitioning(default_kernel_init,
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = jnp.take(table, ids, axis=0).astype(cfg.dtype)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class MTPModule(nn.Module):
    """One multi-token-prediction depth: from the main stack's last
    hidden state h_i (before its final norm) and the embedding of the
    next token t_{i+1}, the hidden state that predicts t_{i+2}."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, h, next_embedding, rope):
        cfg = self.cfg
        with jax.named_scope(tracing.SCOPE_NORM):
            joined = jnp.concatenate(
                [RMSNorm(cfg, name="norm_h")(h),
                 RMSNorm(cfg, name="norm_e")(next_embedding)], axis=-1)
        x = _dense(cfg.hidden_size, cfg, "proj", (None, "embed"),
                   use_bias=False)(joined)
        x = _block(cfg)(cfg, True, name="block")(x, rope)
        with jax.named_scope(tracing.SCOPE_NORM):
            return RMSNorm(cfg, name="final_norm")(x)


class LatentMoELM(nn.Module):
    """Decoder-only causal LM of the block above. Returns the logits
    (B, S, V); with the collection `AUX_COLLECTION` mutable it also runs
    the multi-token-prediction module and sows its logits there
    (position i predicts token i + 2; the last position's input wraps
    around and is not a prediction)."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, ids):
        cfg = self.cfg
        embed = TokenEmbedding(cfg, name="embed")
        head = _dense(cfg.vocab_size, cfg, "lm_head", ("embed", "vocab"),
                      use_bias=False)
        rope = rope_angles(jnp.arange(ids.shape[1]), cfg.qk_rope_head_dim,
                           cfg.rope_theta)
        with jax.named_scope(tracing.SCOPE_EMBED):
            x = embed(ids)
        for i in range(cfg.num_hidden_layers):
            x = _block(cfg)(cfg, i >= cfg.first_k_dense_replace,
                            name=f"layer_{i}")(x, rope)

        def logits_of(hidden):
            return nn.with_logical_constraint(
                head(hidden).astype(cfg.logits_dtype),
                ("batch", "seq", "vocab"))

        with jax.named_scope(tracing.SCOPE_NORM):
            normed = RMSNorm(cfg, name="final_norm")(x)
        logits = logits_of(normed)
        if (cfg.num_nextn_predict_layers
                and self.is_mutable_collection(AUX_COLLECTION)):
            with jax.named_scope(tracing.SCOPE_MTP):
                with jax.named_scope(tracing.SCOPE_EMBED):
                    following = embed(jnp.roll(ids, -1, axis=1))
                hidden = MTPModule(cfg, name="mtp")(x, following, rope)
                self.sow(AUX_COLLECTION, "logits", logits_of(hidden))
        return logits


def _latent_query(cfg, x, q_b, q):
    """A latent attention's queries: `q_b(RMSNorm(x W_qa))`, or `q(x)`
    where `q_lora_rank` is None."""
    if cfg.q_lora_rank is None:
        return q(x)
    c_q = _dense(cfg.q_lora_rank, cfg, "q_a", ("embed", "latent"),
                 use_bias=False)(x)
    return q_b(RMSNorm(cfg, "latent", name="q_a_norm")(c_q))


# The published model (huggingface.co/jdopensource/JoyAI-LLM-Flash,
# config.json): the dataclass's defaults.
LATENT_MOE_CONFIGS = {
    "joyai-llm-flash": LatentMoEConfig(),
    # The same block at a size the CPU tests run.
    "latent-moe-tiny": LatentMoEConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32),
}
