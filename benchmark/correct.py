"""The comparisons that decide `correct`: the trainer's objective against
the plain reference on one seeded sequence, the parameters before the
first step against those after it, the loss after twenty steps against
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

Discrete choices. A model that routes by top-k decides from scores, and
where the k-th and the (k+1)-th score of a token are closer than the
program's rounding error the program and a float32 reference pick
different experts. That is no fault of either, and it is not rare
(a simulation on the CPU, PR 29, kept as tests/benchmarking/
test_benchmark_model_from_files.py::test_choice_tol_separates_...: 4096
tokens of width 2048, a 2048 x 256 router of std 0.02, sigmoid scores,
top 8; the input perturbed by 0.1 / 0.3 / 0.6% and rounded to bf16
changes the expert set of 80 / 129 / 238 tokens; a router in bf16
throughout, input, weights, product and sigmoid, of 765-814 over three
seeds), and one expert swapped at one token moves that token's logits
by 7-26% of the largest logit (issue 29's script, not kept):
LOGITS_TOL could never hold. So the reference does not choose: it takes
the choices the program sowed (README.md, "Discrete choices"), computes
in float32 with exactly that selection, and the logits and gradients
are compared as above. What is left to check is that the choices are
ones rounding can explain: `choice_slack`, the most by which a chosen
item's float32 score lies below the reference's own k-th best, in
standard deviations of the scores. In the same simulation it reads
0.004 / 0.008 / 0.016 for the perturbed input and 0.034-0.035 with the
router in bf16 throughout: the spacing of bf16 just under 1 is 2^-8 =
0.0039, the scores' deviation 0.19, so one rounding of each of the two
scores compared is 0.02. Choosing the k worst reads 4.4-4.5; the
(k+1)-th in place of the k-th at every token 0.227-0.243, the (k+2)-th
0.29-0.33. CHOICE_TOL is 0.1: five bf16 eps of a score scale of 1 at
that deviation, nearly three times the largest sound reading and under
half of the nearest wrong one. A selection that is wrong at a single token by
one rank where two scores tie to rounding is, by construction, not told
from a sound one: nor does it move a logit by more than rounding moves
the scores. Another count of choices than the configuration's k, the
same item twice, or a reference that returns no slack reads NaN and
fails.
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
CHOICE_TOL = 0.1


@jax.jit
def _rel_max(got, want):
    """Largest difference as a share of the largest reference value."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                        for leaf in jax.tree.leaves(tree)))


def choice_slack(scores, chosen, k: int):
    """How far the program's discrete choices lie from the reference's
    own, for one choosing layer: over every choice, the most by which
    the chosen item's score lies below the k-th best score of its
    position, in standard deviations of all the scores; 0 where both
    choose alike. `scores` are the reference's float32 selection scores
    (..., positions, items), `chosen` the int32 items the program sowed
    (..., positions, chosen). A position with another count than `k` of
    chosen items, or the same item twice, gives NaN: fewer experts is
    another model, not a rounding."""
    if chosen.shape[-1] != k:
        return jnp.float32(jnp.nan)
    kth = jax.lax.top_k(scores, k)[0][..., -1:]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    ordered = jnp.sort(chosen, axis=-1)
    twice = jnp.any(ordered[..., 1:] == ordered[..., :-1])
    slack = jnp.max(jnp.maximum(kth - picked, 0.0)) / jnp.std(scores)
    return jnp.where(twice, jnp.nan, slack)


def measure_against_reference(objective, reference, params, dims: dict,
                              seq: int, seed: int, grad_leaves: dict) -> dict:
    """Forward logits at the cell's full sequence length, and the
    gradient of the trainer's objective on its first GRAD_POSITIONS
    positions, program against reference, same parameters, one sequence
    drawn from `seed`. `objective` is `Trainer.objective`; `grad_leaves`
    (name -> path in the parameter tree) are the leaves the
    configuration asks to be compared one by one.

    The program runs once, forward and backward at the full length. With
    a causal mask the first n positions do not see the rest, so the
    reference takes the gradient on the n-token prefix alone (its S x S
    scores of every layer are alive at once in a backward pass); without
    one, n is the full length. The ids are ARGUMENTS of every jitted
    function here: as constants they would put the seed into the program
    and no run would find it in the compile cache. Every comparison is
    reduced on the device; only the errors come back.

    Discrete choices the model sowed go to the reference, sliced to the
    positions it is given: it decides with them, in float32, and says
    how far they lie from its own (`choice_slack`, README.md). A
    reference that returns none where choices were sown reads NaN here,
    and fails."""
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, dims["vocab_size"], size=(1, seq), dtype=np.int32))
    n = min(seq, GRAD_POSITIONS) if dims["causal"] else seq

    @jax.jit
    def program(params, ids):
        def loss(p):
            value, logits, choices = objective(p, ids, n)
            return value, (logits, choices)

        (_, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return aux, grads

    (logits, choices), got = program(params, ids)
    prefix = jax.tree.map(lambda c: c[..., :n, :], choices)
    want = jax.jit(jax.grad(functools.partial(reference.loss, dims=dims)))(
        params, ids[:, :n], choices=prefix)
    want_logits, slack = reference.forward(params, ids, dims, choices)
    errors = {"logits": _rel_max(logits, want_logits)}
    if jax.tree.leaves(choices):
        errors["choice_slack"] = jnp.nan if slack is None else slack
    norm_got, norm_want = _global_norm(got), _global_norm(want)
    errors["grad_norm"] = jnp.abs(norm_got - norm_want) / norm_want
    for name, path in grad_leaves.items():
        errors[f"grad.{name}"] = _rel_max(_leaf(got, path),
                                          _leaf(want, path))
    return {k: float(v) for k, v in jax.device_get(errors).items()}


def tolerance(name: str) -> float:
    """The limit of one entry of `measure_against_reference`."""
    return {"logits": LOGITS_TOL, "choice_slack": CHOICE_TOL}.get(
        name, GRAD_TOL)


def beyond_tolerance(errors: dict) -> dict:
    """The entries of `measure_against_reference` that fail (a NaN
    fails)."""
    return {k: v for k, v in errors.items() if not v <= tolerance(k)}


def loss_in_band(loss: float, recorded: float, band: float) -> bool:
    """Random tokens keep the loss near ln(vocab); this guards against a
    step that trains garbage, the reference comparison is the real
    check. `band` is what five seeds spread, with room (the cell's file
    says how it was measured)."""
    return math.isfinite(loss) and abs(loss - recorded) <= band


def _leaf_bit_sums(params) -> list:
    return [jnp.sum(jax.lax.bitcast_convert_type(
        leaf.astype(jnp.float32), jnp.uint32)) for leaf in
        jax.tree.leaves(params)]


@jax.jit
def _bit_sum(params):
    return sum(_leaf_bit_sums(params))


@jax.jit
def leaf_checksums(params):
    """One wrap-around sum of bit patterns per leaf, in the order of
    `jax.tree.leaves`."""
    return jnp.stack(_leaf_bit_sums(params))


def leaves_unmoved(before, after) -> int:
    """How many leaves have the same `leaf_checksums` before and after a
    step. AdamW's first update moves every element whose gradient is
    not nought by the learning rate, so a sound step leaves none: a step
    that hands its state back unchanged leaves all, a frozen leaf one.
    (Real widths, two layers, on the CPU, PR 29: 0 of 29 leaves in
    gpt2-small and in bert-base; on the chip 0 in every run.)"""
    return int(np.sum(np.asarray(before) == np.asarray(after)))


def replica_checksums(params) -> list[int]:
    """One wrap-around sum of the parameters' bit patterns per device
    that holds a copy, each computed on that device from its own copy:
    equal only if the copies are equal bit for bit (up to a collision
    nobody produces by accident). For replicated parameters."""
    return [int(shard.data) for shard in _bit_sum(params).addressable_shards]
