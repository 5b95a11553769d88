"""Transformer model family (GPT-2 causal LM, BERT encoder, ViT) —
TPU-first flax implementation shared by the model zoo.

The reference frames these as *workloads* (BASELINE.json north-star
configs: BERT-base DistributedGradientTape, GPT-2 1.3B + Adasum; its own
examples are torch/TF scripts, e.g. examples/pytorch_synthetic_benchmark.py).
Here they are first-class models designed for the MXU and for mesh
parallelism:

* bfloat16 activations / fp32 params (MXU-native mixed precision);
* every parameter is annotated with **logical axes** via
  `nn.with_logical_partitioning`; `parallel/sharding.py` maps logical
  axes → mesh axes (tp/ep/pp/...) so one model definition serves 1 chip
  or a v5p-128 without edits;
* activations carry `nn.with_logical_constraint` hints on (batch,
  sequence, embed) so dp/sp sharding propagates through the graph;
* static shapes everywhere; per-layer `nn.remat` option to trade FLOPs
  for HBM; optional `nn.scan` over layers for O(1) compile scaling;
* optional Mixture-of-Experts FFN (Switch-style top-1 routing with
  static capacity) whose expert dim is a logical axis → expert
  parallelism is just a sharding rule.

Logical axis vocabulary (mapped in parallel/sharding.py):
    "batch", "seq", "embed", "mlp", "heads", "kv", "vocab",
    "expert", "expert_mlp", "layers", "stage"
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import tracing
from ..ops.attention import attention

Dtype = Any

default_kernel_init = nn.initializers.normal(stddev=0.02)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Hyperparameters for the transformer family."""

    vocab_size: int = 50257
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 1024
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    causal: bool = True
    # MoE: every `moe_every`-th block uses a Switch FFN with n_experts.
    n_experts: int = 0
    moe_every: int = 2
    capacity_factor: float = 1.25
    # Engineering knobs.
    remat: bool = False
    scan_layers: bool = False
    logits_via_embedding: bool = False
    # Output logits dtype. f32 is the DEFAULT: model.apply logits are a
    # public surface (sampling, logprob extraction, custom losses), and
    # silently narrowing them costs external consumers precision
    # (ADVICE r14). The measured train paths OPT INTO bf16 explicitly
    # (benchmark/configs/, chip_smoke.py, examples/jax_gpt2_train.py):
    # the (B, S, V) logits tensor is the largest activation in the
    # model (1.65 GB in f32 at 8192 tokens of GPT-2's vocabulary) and
    # every loss in this repo upcasts to f32 *inside* its softmax
    # reduction (parallel/train.py softmax_xent), so emitting bf16
    # halves the lm-head region's HBM traffic (its time is the ledger's
    # `loss_head_ms_per_step`) without changing the loss numerics.
    logits_dtype: Dtype = jnp.float32
    # Learned (gpt2/bert/vit) vs fixed sinusoidal positions.
    learned_pos: bool = True
    # Attention implementation: "dense", or the sequence-parallel kernels
    # "ring" (blockwise ppermute) / "ulysses" (all-to-all head exchange).
    # The sp kernels require an ambient mesh (jax.sharding.set_mesh /
    # make_train_step) containing `sp_axis`; they fall back to dense when
    # the axis is absent or trivial.
    attn_impl: str = "dense"
    sp_axis: str = "sp"
    # With attn_impl="ulysses": run the per-head-group attention through
    # the Pallas flash kernel instead of XLA dense (composes sequence
    # parallelism with the fused kernel).
    sp_use_flash: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def _dense(features, cfg: TransformerConfig, name: str, logical_axes,
           use_bias: bool = True):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(default_kernel_init, logical_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (logical_axes[-1],)
        ),
        name=name,
    )


def _attention_dispatch(cfg, q, k, v, mask):
    """`ops/attention.py::attention` told what a configuration of this
    family (or of `models/latent_moe.py`) says about its attention."""
    return attention(q, k, v, causal=cfg.causal, mask=mask,
                     impl=cfg.attn_impl,
                     sp_axis=getattr(cfg, "sp_axis", "sp"),
                     sp_use_flash=getattr(cfg, "sp_use_flash", False))


class MultiHeadAttention(nn.Module):
    """MXU-friendly attention: fused QKV projection, einsum contractions,
    softmax in fp32. Head dim carries the "heads" logical axis so tensor
    parallelism (Megatron-style column/row split) is a sharding rule, and
    the (batch, seq) activation constraint lets dp/sp shard the sequence
    (the jit-visible face of sequence parallelism; ring attention lives
    in parallel/ring.py for shard_map use)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None,
                 deterministic: bool = True):
        cfg = self.cfg
        B, S, D = x.shape
        H, Hd = cfg.n_heads, cfg.head_dim

        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            qkv = nn.DenseGeneral(
                (3, H, Hd),
                axis=-1,
                use_bias=True,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, ("embed", None, "heads", "kv")
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (None, "heads", "kv")
                ),
                name="qkv",
            )(x)
            q, k, v = (jnp.squeeze(a, axis=2)
                       for a in jnp.split(qkv, 3, axis=2))  # (B,S,H,Hd)
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))

        with jax.named_scope(tracing.SCOPE_ATTN_FULL):
            ctx = _attention_dispatch(cfg, q, k, v, mask)
        ctx = nn.with_logical_constraint(ctx, ("batch", "seq", "heads", "kv"))

        with jax.named_scope(tracing.SCOPE_ATTN_PROJ):
            out = nn.DenseGeneral(
                D,
                axis=(-2, -1),
                use_bias=True,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    default_kernel_init, ("heads", "kv", "embed")
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("embed",)
                ),
                name="out",
            )(ctx)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class MlpBlock(nn.Module):
    """Dense FFN: d_model → d_ff (column-split "mlp") → d_model (row-split)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        h = _dense(cfg.d_ff, cfg, "wi", ("embed", "mlp"))(x)
        h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        h = _dense(cfg.d_model, cfg, "wo", ("mlp", "embed"))(h)
        if cfg.dropout_rate > 0.0:
            h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return nn.with_logical_constraint(h, ("batch", "seq", "embed"))


class SwitchMoE(nn.Module):
    """Switch-transformer top-1 MoE FFN with static capacity.

    Expert weights carry the "expert" logical axis — map it to the mesh's
    ep axis and XLA inserts the all-to-all dispatch (the reference's
    `hvd.alltoall` is exactly this primitive; SURVEY.md §2.6 notes MoE as
    an absent-but-enabled strategy there). Dispatch/combine are one-hot
    einsums: static shapes, MXU-friendly, drop-on-overflow.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        B, S, D = x.shape
        E = cfg.n_experts
        T = B * S
        C = max(1, int(cfg.capacity_factor * T / E))  # per-expert capacity

        tokens = x.reshape(T, D)
        gate_logits = _dense(E, cfg, "router", ("embed", None), use_bias=False)(
            tokens
        ).astype(jnp.float32)
        probs = jax.nn.softmax(gate_logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)                  # (T,)
        gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)   # (T,E)
        pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - 1   # (T,E)
        pos = jnp.max(pos_in_expert, axis=-1)                     # (T,)
        keep = (pos >= 0) & (pos < C)

        # dispatch: (T, E, C) one-hot; combine adds the gate weight.
        dispatch = (
            jax.nn.one_hot(expert_idx, E, dtype=cfg.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, -1), C, dtype=cfg.dtype)[:, None, :]
        )
        expert_in = jnp.einsum("td,tec->ecd", tokens.astype(cfg.dtype), dispatch)
        expert_in = nn.with_logical_constraint(expert_in, ("expert", None, "embed"))

        wi = self.param(
            "wi",
            nn.with_logical_partitioning(default_kernel_init,
                                         ("expert", "embed", "expert_mlp")),
            (E, D, cfg.d_ff),
            cfg.param_dtype,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(default_kernel_init,
                                         ("expert", "expert_mlp", "embed")),
            (E, cfg.d_ff, D),
            cfg.param_dtype,
        )
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(cfg.dtype))
        h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("expert", None, "expert_mlp"))
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo.astype(cfg.dtype))

        combine = dispatch * gate.astype(cfg.dtype)[:, None, None]
        out = jnp.einsum("ecd,tec->td", expert_out, combine)
        # Router auxiliary load-balancing loss (Switch eq. 4), stashed for
        # the train step to pick up via mutable "losses" collection.
        density = jnp.mean(onehot.astype(jnp.float32), axis=0)
        density_proxy = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(density * density_proxy)
        self.sow("losses", "moe_aux", aux)
        return out.reshape(B, S, D)


class TransformerBlock(nn.Module):
    """Pre-LN block; `use_moe` swaps the FFN for SwitchMoE. When
    `scanned` the return is the (carry, ys) pair nn.scan requires."""

    cfg: TransformerConfig
    use_moe: bool = False
    scanned: bool = False

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        cfg = self.cfg
        ln = functools_partial_ln(cfg)
        with jax.named_scope(tracing.SCOPE_NORM):
            y = ln(name="ln1")(x)
        h = x + MultiHeadAttention(cfg, name="attn")(y, mask, deterministic)
        with jax.named_scope(tracing.SCOPE_NORM):
            y = ln(name="ln2")(h)
        if self.use_moe:
            out = h + SwitchMoE(cfg, name="moe")(y, deterministic)
        else:
            with jax.named_scope(tracing.SCOPE_MLP):
                y = MlpBlock(cfg, name="mlp")(y, deterministic)
            out = h + y
        out = nn.with_logical_constraint(out, ("batch", "seq", "embed"))
        return (out, None) if self.scanned else out


def functools_partial_ln(cfg: TransformerConfig):
    import functools

    return functools.partial(
        nn.LayerNorm,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(),
                                                ("embed",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                               ("embed",)),
    )


def sinusoidal_positions(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len)[:, None]
    div = np.exp(np.arange(0, d_model, 2) * (-np.log(10000.0) / d_model))
    pe = np.zeros((max_len, d_model), dtype=np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


class Embedder(nn.Module):
    """Token + position embedding; vocab dim is tp-shardable ("vocab")."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, ids):
        cfg = self.cfg
        emb = self.param(
            "embedding",
            nn.with_logical_partitioning(default_kernel_init, ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        x = jnp.take(emb, ids, axis=0).astype(cfg.dtype)
        if cfg.learned_pos:
            pos_emb = self.param(
                "pos_embedding",
                nn.with_logical_partitioning(default_kernel_init, (None, "embed")),
                (cfg.max_len, cfg.d_model),
                cfg.param_dtype,
            )
            x = x + pos_emb[None, : ids.shape[1]].astype(cfg.dtype)
        else:
            pe = sinusoidal_positions(cfg.max_len, cfg.d_model)
            x = x + jnp.asarray(pe[None, : ids.shape[1]], dtype=cfg.dtype)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))

    def attend(self, x):
        emb = self.get_variable("params", "embedding")
        return jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype))


class TransformerStack(nn.Module):
    """The n_layers block stack; optionally nn.scan'd and/or remat'd."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        cfg = self.cfg
        block_cls = TransformerBlock
        if cfg.remat:
            block_cls = nn.remat(
                TransformerBlock,
                prevent_cse=not cfg.scan_layers,
                static_argnums=(3,),
            )
        if cfg.scan_layers and cfg.n_experts == 0:
            # Homogeneous stack → scan for O(1) compile; params gain a
            # leading "layers" axis.
            ScannedBlock = nn.scan(
                block_cls,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            x, _ = ScannedBlock(cfg, False, True, name="layers")(
                x, mask, deterministic
            )
            return x
        for i in range(cfg.n_layers):
            use_moe = (
                cfg.n_experts > 0
                and cfg.moe_every > 0
                and (i % cfg.moe_every == cfg.moe_every - 1)
            )
            x = block_cls(cfg, use_moe, name=f"layer_{i}")(x, mask, deterministic)
        return x


class TransformerLM(nn.Module):
    """Decoder-only causal LM — the GPT-2 shape (flagship model).

    Parity target: reference north-star "GPT-2 1.3B + Adasum grad
    aggregation" (BASELINE.json; SURVEY.md §6)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, ids, mask=None, deterministic: bool = True):
        cfg = self.cfg
        embedder = Embedder(cfg, name="embed")
        with jax.named_scope(tracing.SCOPE_EMBED):
            x = embedder(ids)
        x = TransformerStack(cfg, name="stack")(x, mask, deterministic)
        with jax.named_scope(tracing.SCOPE_NORM):
            x = functools_partial_ln(cfg)(name="ln_f")(x)
        if cfg.logits_via_embedding:
            logits = embedder.attend(x)
        else:
            logits = _dense(cfg.vocab_size, cfg, "lm_head", ("embed", "vocab"),
                            use_bias=False)(x)
        return nn.with_logical_constraint(
            logits.astype(cfg.logits_dtype), ("batch", "seq", "vocab")
        )


class TransformerEncoder(nn.Module):
    """Bidirectional encoder + MLM head — the BERT shape.

    Parity target: reference north-star "BERT-base DistributedGradientTape
    + tensor fusion" (BASELINE.json)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, ids, mask=None, deterministic: bool = True):
        cfg = dataclasses.replace(self.cfg, causal=False)
        with jax.named_scope(tracing.SCOPE_EMBED):
            x = Embedder(cfg, name="embed")(ids)
        x = TransformerStack(cfg, name="stack")(x, mask, deterministic)
        with jax.named_scope(tracing.SCOPE_NORM):
            x = functools_partial_ln(cfg)(name="ln_f")(x)
        logits = _dense(cfg.vocab_size, cfg, "mlm_head", ("embed", "vocab"),
                        use_bias=False)(x)
        return logits.astype(cfg.logits_dtype)


# ---------------------------------------------------------------------------
# Named configs (sizes follow the public GPT-2 / BERT model cards).
GPT2_CONFIGS = {
    "gpt2-tiny": TransformerConfig(vocab_size=1024, d_model=128, n_heads=4,
                                   n_layers=2, d_ff=512, max_len=256),
    "gpt2-small": TransformerConfig(d_model=768, n_heads=12, n_layers=12,
                                    d_ff=3072),
    "gpt2-medium": TransformerConfig(d_model=1024, n_heads=16, n_layers=24,
                                     d_ff=4096),
    "gpt2-large": TransformerConfig(d_model=1280, n_heads=20, n_layers=36,
                                    d_ff=5120),
    "gpt2-xl": TransformerConfig(d_model=1600, n_heads=25, n_layers=48,
                                 d_ff=6400),
    # The north-star 1.3B config (GPT-3 XL shape).
    "gpt2-1p3b": TransformerConfig(d_model=2048, n_heads=16, n_layers=24,
                                   d_ff=8192, max_len=2048),
}

BERT_CONFIGS = {
    "bert-tiny": TransformerConfig(vocab_size=30522, d_model=128, n_heads=2,
                                   n_layers=2, d_ff=512, max_len=128,
                                   causal=False),
    "bert-base": TransformerConfig(vocab_size=30522, d_model=768, n_heads=12,
                                   n_layers=12, d_ff=3072, max_len=512,
                                   causal=False),
    "bert-large": TransformerConfig(vocab_size=30522, d_model=1024, n_heads=16,
                                    n_layers=24, d_ff=4096, max_len=512,
                                    causal=False),
}
