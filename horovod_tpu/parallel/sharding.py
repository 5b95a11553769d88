"""Logical-axis → mesh-axis sharding rules and helpers.

This is the TPU-native replacement for the reference's rank-topology +
backend-selection machinery (SURVEY.md §2.6/§5.8): instead of choosing
NCCL vs MPI per op, you choose *where each named tensor dimension lives
on the mesh*, and XLA inserts the collectives (psum for row-parallel
matmuls, all-to-all for expert dispatch, ...) over ICI/DCN.

Models in horovod_tpu.models annotate parameters and activations with
logical axis names ("embed", "mlp", "heads", "expert", ...). The rules
below map those to the canonical mesh axes (parallel/mesh.py AXIS_ORDER:
pp, dp, ep, sp, tp). Users override per-call for custom layouts.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (logical axis, mesh axes) pairs. A logical axis maps to the first rule
# whose mesh axes are all present in the mesh (flax skips absent axes).
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dp",)),          # batch dim → data parallel
    ("seq", ("sp",)),            # sequence dim → context parallel
    ("embed", None),             # d_model replicated (megatron layout)
    ("mlp", ("tp",)),            # d_ff column-split
    ("heads", ("tp",)),          # attention heads split
    ("kv", None),
    ("latent", None),            # latent attention's low ranks: replicated
    ("vocab", ("tp",)),          # embedding/lm-head vocab split
    ("expert", ("ep",)),         # MoE experts → expert parallel
    ("expert_mlp", ("tp",)),
    ("layers", None),            # scan axis; "pp" when pipeline-sharding
    ("stage", ("pp",)),
)

# Pipeline variant: scan-stacked layer params shard over pp on their
# leading "layers" axis (PipelinedLM regroups them into stages).
PIPELINE_RULES: Tuple[Tuple[str, Any], ...] = tuple(
    ("layers", ("pp",)) if k == "layers" else (k, v) for k, v in DEFAULT_RULES
)

# FSDP-style variant: shard the big replicated dims over dp as well
# (ZeRO-3 analogue — the reference has no equivalent; TPU-native bonus).
FSDP_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dp",)),
    ("seq", ("sp",)),
    ("embed", ("dp",)),
    ("mlp", ("tp",)),
    ("heads", ("tp",)),
    ("kv", None),
    ("latent", None),
    ("vocab", ("tp",)),
    ("expert", ("ep",)),
    ("expert_mlp", ("tp",)),
    ("layers", None),
    ("stage", ("pp",)),
)


def filter_rules(rules: Sequence[Tuple[str, Any]], mesh: Mesh):
    """Drop mesh axes that don't exist in `mesh` (so one rule set serves
    a dp-only mesh and a full pp×dp×ep×sp×tp mesh)."""
    out = []
    for logical, axes in rules:
        if axes is None:
            out.append((logical, None))
            continue
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if a in mesh.axis_names)
        if len(present) == 1:
            out.append((logical, present[0]))
        elif present:
            out.append((logical, present))
        else:
            out.append((logical, None))
    return tuple(out)


def logical_sharding(tree, mesh: Mesh, rules=DEFAULT_RULES):
    """Boxed (LogicallyPartitioned) pytree → NamedSharding pytree."""
    specs = nn.get_partition_spec(tree)
    return nn.logical_to_mesh_sharding(specs, mesh, filter_rules(rules, mesh))


def init_sharded(model, rng, example_inputs, mesh: Mesh, rules=DEFAULT_RULES,
                 **init_kwargs):
    """Initialize model variables directly into their mesh shardings
    (no host round-trip; params larger than one host's RAM stay sharded).

    Returns (variables, shardings) with variables *unboxed* (plain
    arrays, metadata stripped) — downstream code uses the shardings tree.
    """
    abstract = jax.eval_shape(
        lambda r: model.init(r, *example_inputs, **init_kwargs), rng
    )
    # get_partition_spec collapses metadata boxes to PartitionSpec leaves,
    # so the sharding tree matches the *unboxed* variable structure.
    shardings = logical_sharding(abstract, mesh, rules)
    init_fn = jax.jit(
        lambda r: nn.unbox(model.init(r, *example_inputs, **init_kwargs)),
        out_shardings=shardings,
    )
    variables = init_fn(rng)
    return variables, shardings


def batch_spec(mesh: Mesh, shard_seq: bool = False) -> P:
    """PartitionSpec for an input batch: leading dim over dp (and pp's
    microbatch dim is handled by the pipeline layer), sequence dim over
    sp when requested."""
    b = tuple(a for a in ("dp",) if a in mesh.axis_names) or None
    if not shard_seq:
        return P(b)
    s = tuple(a for a in ("sp",) if a in mesh.axis_names) or None
    return P(b, s)
