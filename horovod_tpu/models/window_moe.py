"""Grouped-query decoder whose layers differ by kind: sliding-window
layers among full ones, another head count for each kind, and a routed
feed-forward after a leading dense one; Laguna-XS.2 (33.4B-A3B) is the
registry's entry.

What differs from `models/latent_moe.py`'s block, by mechanism (field
names are the published `config.json` keys unless said otherwise):

* **the layer pattern is data** (`WindowMoEConfig.layers`): layer l is
  `(layer_types[l], num_attention_heads_per_layer[l],
  mlp_layer_types[l])`: its attention kind (`full_attention` or
  `sliding_attention`), its number of query heads (48 on full layers,
  64 on sliding ones) and its feed-forward (`dense` or `sparse`);
* **grouped-query attention with an output gate** (`GatedAttention`):
  `H_l` query heads over `num_key_value_heads` key/value heads of
  `head_dim`, query head h attending key/value head `h // (H_l / H_kv)`;
  causal, and on sliding layers key j is visible to query i iff
  `0 <= i - j < sliding_window`; each head's output is multiplied by
  `sigmoid(x W_g)_h` (one gate a head and position) before the output
  projection. The call goes through `ops/attention.py`, whose flash
  kernels visit only the key tiles a query tile's band intersects;
* **rotary positions by layer kind** (`rope_parameters`): full layers
  rotate the first `partial_rotary_factor` of a head's dims with YaRN
  frequencies (`yarn_inv_freq`: per pair a blend of the plain frequency
  and that frequency over `factor`, by where the pair's wavelength lies
  between `beta_fast` and `beta_slow` turns of the original context)
  and scale cos and sin by `attention_factor`; sliding layers rotate
  all of it at their own `rope_theta`, unscaled. Rotate-half layout:
  pair j is `(x[j], x[j + rot / 2])`;
* the routed layer is `latent_moe.RoutedExperts` itself (a float32
  sigmoid router over all experts, top k, gates normalised and scaled,
  the experts held here plus a shared one), and RMSNorm, the gated
  feed-forward, the embedding and the recomputation policy are that
  module's too. This configuration answers to the names they read:
  `n_routed_experts` (the published count, `num_experts` in Laguna's
  file), `experts_held`, `expert_share`, `routed_scaling_factor`
  (`moe_routed_scaling_factor`), `n_shared_experts` (the shared
  expert's width in units of an expert's).

Discrete choices are sown into the collection `choices` as in
`latent_moe`. Regions of the XLA profile: `hvd.attn.proj`,
`hvd.attn.window`, `hvd.attn.full`, `hvd.moe.route`, `hvd.moe.experts`,
`hvd.mlp`, `hvd.norm`, `hvd.embed` (docs/tracing.md "Under jit").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import telemetry, tracing
from ..ops.attention import attention
from ..ops.flash_attention import attention_scores, key_tiles
from .latent_moe import (
    _KEEP_CHOICES, GatedMLP, RMSNorm, RoutedExperts, TokenEmbedding)
from .transformer import _dense, default_kernel_init

Dtype = Any

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

_TILES_HELP = ("Key tiles one (batch, head) forward sweep of the flash "
               "kernel touches at the sequence length traced (what: "
               "visited, under_diagonal), by layer kind (kind: window, "
               "full)")
_SCORES_HELP = ("Score entries one (batch, head) forward sweep of the flash "
                "kernel computes at the sequence length traced, and the "
                "visible pairs among them (what: computed, visible), by "
                "layer kind (kind: window, full)")


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One layer kind's entry of `rope_parameters`."""

    rope_theta: float = 10000.0
    rope_type: str = "default"          # or "yarn"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}")


@dataclasses.dataclass(frozen=True)
class Layer:
    """One entry of the layer pattern."""

    attention: str      # FULL or SLIDING
    heads: int          # query heads
    mlp: str            # DENSE or SPARSE


def _pattern(period: tuple, first: tuple, n: int) -> tuple:
    return (first + period * n)[:n]


# Laguna-XS.2's `rope_parameters` (the group also repeats
# `original_max_position_embeddings` at its top level).
_LAGUNA_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    """Hyperparameters; defaults are Laguna-XS.2's. The per-layer lists
    may be longer than `num_hidden_layers` (a cut in depth keeps the
    published lists and reads their first entries)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    layer_types: tuple = _pattern((SLIDING,) * 3 + (FULL,), (FULL,), 40)
    num_attention_heads_per_layer: tuple = _pattern((64,) * 3 + (48,),
                                                    (48,), 40)
    mlp_layer_types: tuple = _pattern((SPARSE,), (DENSE,), 40)
    rope_parameters: Any = None          # mapping kind -> Rotary's fields
    gating: bool = True
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # What `flops_moe`'s helpers read beside the pattern; checked
    # against it.
    first_k_dense_replace: Optional[int] = None
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
        fix = lambda name, value: object.__setattr__(self, name, value)
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            fix(name, tuple(getattr(self, name)))
        rope = self.rope_parameters or _LAGUNA_ROPE
        fix("rope_parameters", tuple(
            (kind, v if isinstance(v, Rotary) else Rotary(**v))
            for kind, v in dict(rope).items() if isinstance(v, (Mapping,
                                                                Rotary))))
        layers = self.layers
        if len(layers) != self.num_hidden_layers:
            raise ValueError("the per-layer lists are shorter than "
                             f"num_hidden_layers={self.num_hidden_layers}")
        for layer in layers:
            if layer.attention not in dict(self.rope_parameters):
                raise ValueError(f"no rope_parameters for {layer.attention!r}")
            if layer.mlp not in (DENSE, SPARSE):
                raise ValueError(f"mlp_layer_types entry {layer.mlp!r}")
            if layer.heads % self.num_key_value_heads:
                raise ValueError(
                    f"{layer.heads} query heads over "
                    f"{self.num_key_value_heads} key/value heads")
        dense = sum(layer.mlp == DENSE for layer in layers)
        if any(layer.mlp == DENSE for layer in layers[dense:]) or (
                self.first_k_dense_replace not in (None, dense)):
            raise ValueError("dense feed-forwards lead, and "
                             f"first_k_dense_replace counts them ({dense})")
        if self.num_nextn_predict_layers:
            raise ValueError("no multi-token-prediction module here")
        held = self.held
        if self.n_routed_experts % held or not (
                0 <= self.expert_share < self.n_routed_experts // held):
            raise ValueError(
                f"experts_held={held} must divide n_routed_experts="
                f"{self.n_routed_experts}, and expert_share="
                f"{self.expert_share} must name one of the shares")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is a whole number of "
                             "experts wide")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl {self.attn_impl!r}: dense or flash")
        if not (self.causal and self.gating):
            raise ValueError("a causal decoder with gated attention")

    @property
    def layers(self) -> tuple:
        """The layer pattern, one `Layer` a layer."""
        return tuple(
            Layer(*entry) for entry in zip(
                self.layer_types, self.num_attention_heads_per_layer,
                self.mlp_layer_types))[:self.num_hidden_layers]

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def n_shared_experts(self) -> int:
        return self.shared_expert_intermediate_size // self.moe_intermediate_size

    def rotary(self, kind: str) -> Rotary:
        return dict(self.rope_parameters)[kind]


# --------------------------------------------------------------- positions

def yarn_inv_freq(rot: int, rope: Rotary) -> np.ndarray:
    """(rot / 2,) frequencies of a YaRN-scaled rotary slice `rot` wide.
    Pair j of plain rotary turns by `theta^(-2j / rot)` a position, a
    wavelength of `2 pi theta^(2j / rot)` positions. Pairs that make
    more than `beta_fast` turns within the original context keep that
    frequency, pairs that make fewer than `beta_slow` get it divided by
    `factor` (positions interpolated), and a linear ramp over the pair
    index blends the two between."""
    plain = rope.rope_theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def pair_of(turns):
        return (rot * math.log(rope.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(rope.rope_theta)))

    low = max(math.floor(pair_of(rope.beta_fast)), 0)
    high = min(math.ceil(pair_of(rope.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    interpolated = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    return plain / rope.factor * interpolated + plain * (1 - interpolated)


def rotary_tables(positions, head_dim: int, rope: Rotary):
    """(cos, sin), each (S, rot / 2) float32, of one layer kind, scaled
    by its `attention_factor`; rot = `partial_rotary_factor` x
    `head_dim`."""
    rot = int(head_dim * rope.partial_rotary_factor)
    if rope.rope_type == "yarn":
        inv_freq = yarn_inv_freq(rot, rope)
    else:
        inv_freq = rope.rope_theta ** (
            -np.arange(0, rot, 2, dtype=np.float64) / rot)
    angles = (positions.astype(jnp.float32)[:, None]
              * jnp.asarray(inv_freq, jnp.float32)[None, :])
    scale = jnp.float32(rope.attention_factor)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rotary(x, cos, sin):
    """Rotate-half: pairs (x[j], x[j + rot / 2]) of the first rot =
    2 x cos.shape[-1] dims of the last axis; the rest passes. x:
    (B, S, H, D)."""
    half = cos.shape[-1]
    a, b = (x[..., :half].astype(jnp.float32),
            x[..., half:2 * half].astype(jnp.float32))
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., 2 * half:]],
                           axis=-1)


# ------------------------------------------------------------------ pieces

class GatedAttention(nn.Module):
    """Grouped-query attention of one layer kind with a per-head output
    gate (module docstring)."""

    cfg: WindowMoEConfig
    layer: Layer

    @nn.compact
    def __call__(self, x, rope):
        cfg, H = self.cfg, self.layer.heads
        S = x.shape[1]
        sliding = self.layer.attention == SLIDING
        window = cfg.sliding_window if sliding else None
        if cfg.attn_impl == "flash":
            kind = "window" if sliding else "full"
            for what, n in zip(("visited", "under_diagonal"), key_tiles(
                    S, cfg.head_dim, cfg.head_dim, window)):
                telemetry.gauge("horovod_attention_key_tiles", _TILES_HELP,
                                {"kind": kind, "what": what}).set(n)
            for what, n in zip(("computed", "visible"), attention_scores(
                    S, cfg.head_dim, cfg.head_dim, window)):
                telemetry.gauge("horovod_attention_scores", _SCORES_HELP,
                                {"kind": kind, "what": what}).set(n)

        def heads(n, name):
            return nn.DenseGeneral(
                (n, cfg.head_dim), axis=-1, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, ("embed", "heads", "kv")),
                name=name)

        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            q = apply_rotary(heads(H, "q")(x), *rope)            # (B,S,H,D)
            k = apply_rotary(heads(cfg.num_key_value_heads, "k")(x), *rope)
            v = heads(cfg.num_key_value_heads, "v")(x)
            gate = jax.nn.sigmoid(_dense(H, cfg, "gate", ("embed", "heads"),
                                         use_bias=False)(x))      # (B,S,H)
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
        with jax.named_scope(tracing.SCOPE_ATTN_WINDOW if sliding
                             else tracing.SCOPE_ATTN_FULL):
            ctx = attention(q, k, v, causal=True, window=window,
                            impl=cfg.attn_impl)
        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            out = nn.DenseGeneral(
                cfg.hidden_size, axis=(-2, -1), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, ("heads", "kv", "embed")),
                name="o")(ctx * gate[..., None])
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class Block(nn.Module):
    """h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h)), attention
    and feed-forward of the kinds `layer` names."""

    cfg: WindowMoEConfig
    layer: Layer

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.cfg
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="attn_norm")(x)
        h = x + GatedAttention(cfg, self.layer, name="attn")(y, rope)
        with jax.named_scope(tracing.SCOPE_NORM):
            y = RMSNorm(cfg, name="ffn_norm")(h)
        if self.layer.mlp == SPARSE:
            out = h + RoutedExperts(cfg, name="moe")(y)
        else:
            with jax.named_scope(tracing.SCOPE_MLP):
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(y)
            out = h + y
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


def _block(cfg: WindowMoEConfig):
    """`Block`, recomputed in the backward pass where `cfg.remat`: all of
    it but the routed layer's discrete choices (`latent_moe`'s policy
    object, so that jax keeps one copy of every jitted call inside)."""
    if not cfg.remat:
        return Block
    return nn.remat(Block, prevent_cse=True, policy=_KEEP_CHOICES)


class WindowMoELM(nn.Module):
    """Decoder-only causal LM of the blocks above; returns the logits
    (B, S, V)."""

    cfg: WindowMoEConfig

    @nn.compact
    def __call__(self, ids):
        cfg = self.cfg
        positions = jnp.arange(ids.shape[1])
        ropes = {kind: rotary_tables(positions, cfg.head_dim, rope)
                 for kind, rope in cfg.rope_parameters}
        with jax.named_scope(tracing.SCOPE_EMBED):
            x = TokenEmbedding(cfg, name="embed")(ids)
        for i, layer in enumerate(cfg.layers):
            x = _block(cfg)(cfg, layer, name=f"layer_{i}")(
                x, ropes[layer.attention])
        with jax.named_scope(tracing.SCOPE_NORM):
            x = RMSNorm(cfg, name="final_norm")(x)
        logits = _dense(cfg.vocab_size, cfg, "lm_head", ("embed", "vocab"),
                        use_bias=False)(x)
        return nn.with_logical_constraint(
            logits.astype(cfg.logits_dtype), ("batch", "seq", "vocab"))


# The published model (huggingface.co/poolside/Laguna-XS.2, config.json):
# the dataclass's defaults.
WINDOW_MOE_CONFIGS = {
    "laguna-xs2": WindowMoEConfig(),
    # The same blocks at a size the CPU tests run: a full dense layer,
    # a sliding routed one, a full routed one; groups of 2 and 3 query
    # heads; a window of 8; YaRN past 16 positions.
    "window-moe-tiny": WindowMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_key_value_heads=2, head_dim=16,
        sliding_window=8, layer_types=(FULL, SLIDING, FULL),
        num_attention_heads_per_layer=(4, 6, 4),
        mlp_layer_types=(DENSE, SPARSE, SPARSE),
        rope_parameters={
            FULL: dict(_LAGUNA_ROPE[FULL], rope_theta=10000, factor=8,
                       original_max_position_embeddings=16, beta_fast=4,
                       attention_factor=1.2079),
            SLIDING: _LAGUNA_ROPE[SLIDING]},
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32),
}
