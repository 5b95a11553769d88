"""Hybrid linear-attention mixture-of-experts decoder: most layers mix
tokens with a delta-rule recurrence, one in every few with latent
attention without positions; Kimi-Linear-48B-A3B is the registry's entry.

What differs from `models/latent_moe.py`'s block, by mechanism (field
names are the published `config.json` keys unless said otherwise):

* **the layer pattern is data** (`LinearMoEConfig.layers`): layer l
  (1-based in the published lists) mixes with Kimi Delta Attention
  where `linear_attn_config["kda_layers"]` holds it and with latent
  attention where `linear_attn_config["full_attn_layers"]` does; its
  feed-forward is dense for the first `first_k_dense_replace` layers
  and routed after;
* **Kimi Delta Attention** (`DeltaAttention`, `num_heads` heads of
  `head_dim` from `linear_attn_config`): q, k, v are projections of x,
  each through a causal depthwise convolution of width
  `short_conv_kernel_size` and SiLU, q and k L2-normalised per head; a
  decay for every key channel `g = -exp(A_log[h]) softplus(x W_fa W_fb
  + dt_bias)` and a write strength `beta = sigmoid(x W_b)`; the gated
  delta rule over the sequence (`ops/kda.py`); each head's output
  RMS-normalised over its channels, multiplied by
  `sigmoid(x W_ga W_gb)` and projected out. The state is a (key, value)
  matrix a head, whatever the sequence's length;
* **latent attention without positions** (`mla_use_nope`):
  `latent_moe.LatentAttention` with a direct query projection
  (`q_lora_rank` None) and no rotary: the 64-wide `k_rope` slice is
  shared by all heads as it is, unturned;
* the routed layer, RMSNorm, the gated feed-forward, the embedding and
  the recomputation policy are `latent_moe`'s. This configuration
  answers to the names they read: `n_routed_experts` (`num_experts` in
  the published file), `num_experts_per_tok` (`num_experts_per_token`),
  `n_shared_experts` (`num_shared_experts`), `norm_topk_prob`
  (`moe_renormalize`); the router is sigmoid
  (`moe_router_activation_func`), one expert group.

Discrete choices are sown into the collection `choices` as in
`latent_moe`. Regions of the XLA profile: `hvd.attn.proj`,
`hvd.attn.kda`, `hvd.attn.latent`, `hvd.moe.route`, `hvd.moe.experts`,
`hvd.mlp`, `hvd.norm`, `hvd.embed` (docs/tracing.md "Under jit").
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import telemetry, tracing
from ..ops import kda
from .latent_moe import (
    _KEEP_CHOICES, GatedMLP, LatentAttention, RMSNorm, RoutedExperts,
    TokenEmbedding)
from .transformer import _dense

Dtype = Any

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"

# Kimi-Linear-48B-A3B's `linear_attn_config`.
_KIMI_LINEAR_ATTN = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "head_dim": 128,
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    "num_heads": 32,
    "short_conv_kernel_size": 4,
}

_CHUNKS_HELP = ("Chunks one sequence of a delta-rule attention's kernel "
                "call is cut into, at the sequence length traced")
_STATE_HELP = ("Bytes of float32 states of a delta-rule attention's kernel "
               "call at the shape traced (what: carried, one a batch row and "
               "head, chunk to chunk; stored, every chunk's starting state, "
               "written for the backward pass)")
_HEADS_HELP = ("Heads one program of a delta-rule attention's kernels "
               "takes, at the head count and widths traced")

# The initial ranges of the decay's parameters (the family's convention,
# as Mamba's): exp(A_log) uniform in [1, 16] a head; softplus(dt_bias)
# log-uniform in [0.001, 0.1] a channel.
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


@dataclasses.dataclass(frozen=True)
class Layer:
    """One entry of the layer pattern."""

    mixer: str          # KDA or MLA
    mlp: str            # DENSE or SPARSE


@dataclasses.dataclass(frozen=True)
class LinearMoEConfig:
    """Hyperparameters; defaults are Kimi-Linear-48B-A3B's."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: Any = None      # mapping, `_KIMI_LINEAR_ATTN`'s keys
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 0
    # The share of a layer's routed experts this program holds, as
    # `LatentMoEConfig`'s.
    experts_held: Optional[int] = None
    expert_share: int = 0
    # Engineering knobs, as `LatentMoEConfig`'s.
    causal: bool = True
    attn_impl: str = "dense"      # or "flash" (ops/flash_attention.py)
    remat: bool = False           # recompute each block in the backward pass
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    logits_dtype: Dtype = jnp.float32

    def __post_init__(self):
        linear = dict(self.linear_attn_config or _KIMI_LINEAR_ATTN)
        object.__setattr__(self, "linear_attn_config", tuple(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in sorted(linear.items())))
        kda_layers, full = set(linear["kda_layers"]), set(
            linear["full_attn_layers"])
        for n in range(1, self.num_hidden_layers + 1):
            if (n in kda_layers) == (n in full):
                raise ValueError(f"layer {n} must be in exactly one of "
                                 "kda_layers and full_attn_layers")
        held = self.held
        if self.n_routed_experts % held or not (
                0 <= self.expert_share < self.n_routed_experts // held):
            raise ValueError(
                f"experts_held={held} must divide n_routed_experts="
                f"{self.n_routed_experts}, and expert_share="
                f"{self.expert_share} must name one of the shares")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl {self.attn_impl!r}: dense or flash")
        if not (self.causal and self.mla_use_nope
                and self.q_lora_rank is None):
            raise ValueError("a causal decoder whose latent attention has no "
                             "positions and no query low rank")
        if self.num_nextn_predict_layers:
            raise ValueError("no multi-token-prediction module here")

    @property
    def linear(self) -> dict:
        return dict(self.linear_attn_config)

    @property
    def layers(self) -> tuple:
        """The layer pattern, one `Layer` a layer."""
        kda_layers = self.linear["kda_layers"]
        return tuple(
            Layer(KDA if n in kda_layers else MLA,
                  DENSE if n <= self.first_k_dense_replace else SPARSE)
            for n in range(1, self.num_hidden_layers + 1))

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts


# ---------------------------------------------------------------- pieces

def _uniform(low: float, high: float, transform=lambda x: x):
    def init(rng, shape, dtype):
        return transform(jax.random.uniform(rng, shape, jnp.float32, low,
                                            high)).astype(dtype)
    return init


def _inverse_softplus_of_log_uniform(x):
    """dt = exp(x); softplus^-1(dt) = dt + log(-expm1(-dt))."""
    dt = jnp.exp(x)
    return dt + jnp.log(-jnp.expm1(-dt))


# The delta-rule block's elementwise steps are each a `jax.checkpoint`:
# what the backward pass keeps of them is their inputs, not their
# intermediates, which a recomputed block's backward pass would
# otherwise hold beside the routed layer's buffers. Heads stay column blocks of (B, S, H * D)
# from the projections to `o`: a 4-D view of that layout is a copy.

@jax.checkpoint
def _conv_silu(x, w):
    """SiLU of x (B, S, C) through a causal depthwise convolution w (K, C)
    summed in float32, in x's dtype."""
    S, width = x.shape[1], w.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + S] * w[i].astype(jnp.float32)
                           for i in range(width))).astype(x.dtype)


@jax.checkpoint
def _decay(f, a_log, dt_bias):
    """g = -exp(A_log[h]) softplus(f + dt_bias), float32, (B, S, H * D)
    from f (B, S, H * D), channel c in head c // D."""
    per_channel = jnp.repeat(jnp.exp(a_log), f.shape[-1] // a_log.shape[0])
    return -per_channel * jax.nn.softplus(f.astype(jnp.float32) + dt_bias)


class ShortConv(nn.Module):
    """A causal depthwise convolution over the sequence, no bias, then
    SiLU: y_t = silu(sum_i w_i * x_(t - K + 1 + i)), kernel (K, channels)
    drawn as a depthwise `Conv1d`'s, uniform within 1 / sqrt(K)."""

    width: int
    param_dtype: Dtype

    @nn.compact
    def __call__(self, x):
        bound = 1.0 / math.sqrt(self.width)
        w = self.param("kernel", nn.with_logical_partitioning(
            _uniform(-bound, bound), (None, "heads")),
            (self.width, x.shape[2]), self.param_dtype)
        return _conv_silu(x, w)


class DeltaAttention(nn.Module):
    """Kimi Delta Attention of one layer (module docstring)."""

    cfg: LinearMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, _ = x.shape
        H, D = cfg.linear["num_heads"], cfg.linear["head_dim"]
        width = H * D
        chunks = kda.chunks_of(S)
        telemetry.gauge("horovod_kda_chunks", _CHUNKS_HELP).set(chunks)
        telemetry.gauge("horovod_kda_heads_per_program", _HEADS_HELP).set(
            kda.heads_per_program(H, D, D))
        carried = B * kda.state_bytes(H, D, D)
        for what, n in (("carried", carried), ("stored", carried * chunks)):
            telemetry.gauge("horovod_kda_state_bytes", _STATE_HELP,
                            {"what": what}).set(n)

        def dense(features, name, axes):
            return _dense(features, cfg, name, axes, use_bias=False)

        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            q = dense(width, "q", ("embed", "heads"))(x)
            k = dense(width, "k", ("embed", "heads"))(x)
            v = dense(width, "v", ("embed", "heads"))(x)
            f = dense(width, "f_b", ("latent", "heads"))(
                dense(D, "f_a", ("embed", "latent"))(x))
            b = dense(H, "b", ("embed", None))(x)
            gate = dense(width, "g_b", ("latent", "heads"))(
                dense(D, "g_a", ("embed", "latent"))(x))
        with jax.named_scope(tracing.SCOPE_ATTN_KDA):
            conv = functools.partial(ShortConv,
                                     cfg.linear["short_conv_kernel_size"],
                                     cfg.param_dtype)
            a_log = self.param("A_log", nn.with_logical_partitioning(
                _uniform(*A_RANGE, jnp.log), ("heads",)), (H,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.with_logical_partitioning(
                _uniform(*(math.log(t) for t in DT_RANGE),
                         _inverse_softplus_of_log_uniform), ("heads",)),
                (width,), jnp.float32)
            scale = self.param("o_norm", nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("kv",)), (D,), cfg.param_dtype)
            # q and k are L2-normed per head, and the output RMS-normed
            # per head and gated, inside the kernels.
            o = kda.kda(conv(name="q_conv")(q), conv(name="k_conv")(k),
                        conv(name="v_conv")(v), _decay(f, a_log, dt_bias),
                        jax.nn.sigmoid(b.astype(jnp.float32)), gate=gate,
                        scale=scale, eps=cfg.rms_norm_eps)
        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            out = dense(cfg.hidden_size, "o", ("heads", "embed"))(o)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class Block(nn.Module):
    """h = x + Mixer(RMSNorm(x)); out = h + FFN(RMSNorm(h)), mixer and
    feed-forward of the kinds `layer` names."""

    cfg: LinearMoEConfig
    layer: Layer

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="attn_norm")(x)
        if self.layer.mixer == KDA:
            h = x + DeltaAttention(cfg, name="attn")(y)
        else:
            h = x + LatentAttention(cfg, name="attn")(y, None)
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="ffn_norm")(h)
        if self.layer.mlp == SPARSE:
            out = h + RoutedExperts(cfg, name="moe")(y)
        else:
            with jax.named_scope(tracing.SCOPE_MLP):
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(y)
            out = h + y
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


def _block(cfg: LinearMoEConfig):
    """`Block`, recomputed in the backward pass where `cfg.remat`: all of
    it but the routed layer's discrete choices (`latent_moe`'s policy
    object, so that jax keeps one copy of every jitted call inside)."""
    if not cfg.remat:
        return Block
    return nn.remat(Block, prevent_cse=True, policy=_KEEP_CHOICES)


class LinearMoELM(nn.Module):
    """Decoder-only causal LM of the blocks above; returns the logits
    (B, S, V)."""

    cfg: LinearMoEConfig

    @nn.compact
    def __call__(self, ids):
        cfg = self.cfg
        with jax.named_scope(tracing.SCOPE_EMBED):
            x = TokenEmbedding(cfg, name="embed")(ids)
        for i, layer in enumerate(cfg.layers):
            x = _block(cfg)(cfg, layer, name=f"layer_{i}")(x)
        with jax.named_scope(tracing.SCOPE_NORM):
            x = RMSNorm(cfg, name="final_norm")(x)
        logits = _dense(cfg.vocab_size, cfg, "lm_head", ("embed", "vocab"),
                        use_bias=False)(x)
        return nn.with_logical_constraint(
            logits.astype(cfg.logits_dtype), ("batch", "seq", "vocab"))


# The published model (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-
# Instruct, config.json): the dataclass's defaults.
LINEAR_MOE_CONFIGS = {
    "kimi-linear-48b-a3b": LinearMoEConfig(),
    # The same blocks at a size the CPU tests run: delta-rule, delta-rule
    # (routed), latent (routed).
    "linear-moe-tiny": LinearMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        linear_attn_config={"full_attn_layers": [3], "kda_layers": [1, 2],
                            "head_dim": 16, "num_heads": 4,
                            "short_conv_kernel_size": 4},
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32),
}
