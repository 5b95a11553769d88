"""The comparisons that decide `correct`: the program against the plain
reference on one seeded sequence, the loss after twenty steps against
the value recorded for the cell, and replicas against each other.

Tolerances, and why. The program computes activations in bfloat16 (8
bits of mantissa, eps = 2^-8 = 0.0039) from float32 parameters; the
reference computes everything in float32 at the highest precision.
Every activation is rounded to bf16 a few times per block, the errors
add up roughly as a random walk over 12 blocks, and the logits are
rounded to bf16 once more — so logits agree to a few eps OF THE LARGEST
LOGIT, as `chip_smoke.py` argues for the attention kernel alone. Five
eps (0.02) holds that with room; an 8-bit float (eps 2^-4), a dropped
causal mask or a dropped layer changes logits by tens of percent and
fails it (tests/benchmarking/test_benchmark_harness.py drops the mask).
Gradients pass through the same roundings twice (forward and backward),
so they get twice that, relative to the largest entry of the leaf and,
for the global norm, relative to the norm.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LOGITS_TOL = 0.02
GRAD_TOL = 0.04
GRAD_POSITIONS = 1024


@jax.jit
def _rel_max(got, want):
    """Largest difference as a share of the largest reference value."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def named_leaves(dims: dict) -> dict:
    """The three leaves the gradient comparison names: the one the
    backward pass reaches last (embedding), a matrix in the middle of
    the stack, and the last LayerNorm's scale."""
    middle = f"layer_{dims['n_layers'] // 2}"
    return {
        "embedding": ("embed", "embedding"),
        f"{middle}.qkv": ("stack", middle, "attn", "qkv", "kernel"),
        "ln_f.scale": ("ln_f", "scale"),
    }


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                        for leaf in jax.tree.leaves(tree)))


def measure_against_reference(apply_fn, loss_fn, reference, params,
                              dims: dict, seq: int, seed: int) -> dict:
    """Forward logits at the cell's full sequence length, and the
    gradient of the loss on its first GRAD_POSITIONS positions, program
    against reference, same parameters, one sequence drawn from `seed`.

    The program runs once, forward and backward at the full length. With
    a causal mask the first n positions do not see the rest, so the
    reference takes the gradient on the n-token prefix alone (its S x S
    scores of every layer are alive at once in a backward pass); without
    one, n is the full length. The ids are ARGUMENTS of every jitted
    function here: as constants they would put the seed into the program
    and no run would find it in the compile cache. Every comparison is
    reduced on the device; only the errors come back."""
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, dims["vocab_size"], size=(1, seq), dtype=np.int32))
    n = min(seq, GRAD_POSITIONS) if dims["causal"] else seq

    @jax.jit
    def program(params, ids):
        def loss(p):
            logits = apply_fn(p, ids)
            return loss_fn(logits[:, :n], ids[:, :n]), logits

        (_, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return logits, grads

    logits, got = program(params, ids)
    want = jax.jit(jax.grad(functools.partial(reference.loss, dims=dims)))(
        params, ids[:, :n])
    errors = {"logits": _rel_max(logits,
                                 reference.forward(params, ids, dims))}
    norm_got, norm_want = _global_norm(got), _global_norm(want)
    errors["grad_norm"] = jnp.abs(norm_got - norm_want) / norm_want
    for name, path in named_leaves(dims).items():
        errors[f"grad.{name}"] = _rel_max(_leaf(got, path),
                                          _leaf(want, path))
    return {k: float(v) for k, v in jax.device_get(errors).items()}


def beyond_tolerance(errors: dict) -> dict:
    """The entries of `measure_against_reference` that fail (a NaN
    fails)."""
    return {k: v for k, v in errors.items()
            if not v <= (LOGITS_TOL if k == "logits" else GRAD_TOL)}


def loss_in_band(loss: float, recorded: float, band: float) -> bool:
    """Random tokens keep the loss near ln(vocab); this guards against a
    step that trains garbage, the reference comparison is the real
    check. `band` is what five seeds spread, with room (the cell's file
    says how it was measured)."""
    return math.isfinite(loss) and abs(loss - recorded) <= band


@jax.jit
def _bit_sum(params):
    return sum(jnp.sum(jax.lax.bitcast_convert_type(
        leaf.astype(jnp.float32), jnp.uint32)) for leaf in
        jax.tree.leaves(params))


def replica_checksums(params) -> list[int]:
    """One wrap-around sum of the parameters' bit patterns per device
    that holds a copy, each computed on that device from its own copy:
    equal only if the copies are equal bit for bit (up to a collision
    nobody produces by accident). For replicated parameters."""
    return [int(shard.data) for shard in _bit_sum(params).addressable_shards]
