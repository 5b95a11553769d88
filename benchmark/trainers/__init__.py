"""Trainers: the ways a user of horovod_tpu spells a training step.

A traffic file names one (`"trainer"`). Each module exposes

    build(model, phase, devices, seed) -> Trainer

where `model` is the flax module of the cell's configuration, `phase`
holds `seq`, `batch_per_chip` and `mesh`, and `devices` are the chips
the phase runs on. Everything a trainer does to the program (meshes,
optimizer wrappers, `hvd.init`) happens inside `build`; the harness only
calls what comes back.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import optax

# One optimizer for every trainer, so that two trainers on one cell
# shape differ in the spelling alone.
LEARNING_RATE = 1e-4


def optimizer() -> optax.GradientTransformation:
    return optax.adamw(LEARNING_RATE)


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
    # state -> values that are equal exactly when the trainer's replicas
    # agree (one per device for replicated parameters).
    checksums: Callable[[Any], list]
    # Undo what build did to the process (e.g. hvd.shutdown).
    close: Callable[[], None] = lambda: None
