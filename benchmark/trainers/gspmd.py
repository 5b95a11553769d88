"""The GSPMD trainer: `create_mesh` -> `make_train_step`, as bench.py,
chip_smoke.py (a) and examples/jax_gpt2_train.py spell it. XLA derives
the gradient all-reduce from the shardings."""
from __future__ import annotations

import functools

import jax
import numpy as np

from benchmark.correct import replica_checksums
from benchmark.trainers import Trainer, optimizer
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import lm_loss, make_train_step


def build(model, phase: dict, devices, seed: int) -> Trainer:
    mesh = create_mesh(phase["mesh"], devices=devices)
    global_batch = phase["batch_per_chip"] * mesh.shape.get("dp", 1)
    example = np.zeros((global_batch, phase["seq"]), np.int32)
    rng = jax.random.PRNGKey(seed)
    init_fn, step_fn, _ = make_train_step(
        model, optimizer(), lm_loss, mesh=mesh)(rng, example)
    batch_sharding = step_fn.shardings[1]
    return Trainer(
        global_batch=global_batch,
        init=functools.partial(init_fn, rng),
        step=step_fn,
        put=lambda ids: jax.device_put(ids, batch_sharding),
        params=lambda state: state.params,
        checksums=lambda state: replica_checksums(state.params),
    )
