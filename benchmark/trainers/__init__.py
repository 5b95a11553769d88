"""Trainers: the ways a user of horovod_tpu spells a training step.

A traffic file names one (`"trainer"`). Each module exposes

    build(model, phase, devices, seed) -> Trainer

where `model` is the flax module of the cell's configuration, `phase`
holds `seq`, `batch_per_chip` and `mesh`, and `devices` are the chips
the phase runs on. Everything a trainer does to the program (meshes,
optimizer wrappers, `hvd.init`) happens inside `build`; the harness only
calls what comes back.

The objective. A trainer knows what its step differentiates, so the
comparison with the plain reference takes it from the trainer
(`Trainer.objective`) and never rebuilds it: a step with a second term
in its loss (a weighted auxiliary loss, a multi-token-prediction head)
is compared with that term, and `reference/<name>.py`'s `loss` is its
float32 counterpart. `lm_objective` below is the one both trainers here
train: `lm_loss` over `model.apply`, the pieces their steps are made
of. It applies the model with `mutable=[CHOICES]`: a model that makes
discrete choices (top-k routing) sows them there (README.md, "Discrete
choices"); a model that does not sows nothing and the collection comes
back empty.

A step that compiles without a chip. A trainer whose step is a
`jax.jit` function over state and batch shardings, as
`make_train_step`'s is, also exposes at module level

    lower(model, phase, devices) -> jax.stages.Lowered

the step lowered for `devices` from shapes alone, nothing executed.
tests/benchmarking/test_benchmark_cells_compile_for_v5e.py compiles
every cell whose trainer module has a `lower` for a described TPU v5e;
a trainer without one (`hvd`: `wrap_step` builds its program on the
first call, from live arrays) is left to the chip.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import optax

# The flax collection a model sows its discrete choices into.
CHOICES = "choices"

# One optimizer for every trainer, so that two trainers on one cell
# shape differ in the spelling alone.
LEARNING_RATE = 1e-4


def optimizer() -> optax.GradientTransformation:
    return optax.adamw(LEARNING_RATE)


def lm_objective(model, loss_fn) -> Callable:
    """`Trainer.objective` of a step that trains `loss_fn(logits, ids)`
    over `model.apply` and nothing else."""

    def objective(params, ids, n):
        logits, sown = model.apply({"params": params}, ids, mutable=[CHOICES])
        return (loss_fn(logits[:, :n], ids[:, :n]), logits,
                sown.get(CHOICES, {}))

    return objective


@dataclasses.dataclass(frozen=True)
class Trainer:
    # Rows of one global batch (the harness draws the token ids).
    global_batch: int
    # () -> state: parameters and optimizer state made on the device
    # from PRNGKey(seed). Callable again after the state was dropped.
    init: Callable[[], Any]
    # (state, batch) -> (state, loss); returns without waiting.
    step: Callable[[Any, Any], tuple]
    # host batch -> device batch, `jax.device_put` with the trainer's
    # batch sharding.
    put: Callable[[Any], Any]
    # state -> the model's parameter tree.
    params: Callable[[Any], Any]
    # (params, ids, n) -> (loss, logits, choices): what the step
    # differentiates, computed at the full length of `ids` with the loss
    # taken on the first n positions; beside it the logits of every
    # position and the collection CHOICES as the model sowed it ({} for
    # a model that chooses nothing).
    objective: Callable[[Any, Any, int], tuple]
    # state -> values that are equal exactly when the trainer's replicas
    # agree (one per device for replicated parameters).
    checksums: Callable[[Any], list]
    # Undo what build did to the process (e.g. hvd.shutdown).
    close: Callable[[], None] = lambda: None
