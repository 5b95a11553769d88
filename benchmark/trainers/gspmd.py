"""The GSPMD trainer: `create_mesh` -> `make_train_step`, as bench.py,
chip_smoke.py (a) and examples/jax_gpt2_train.py spell it. XLA derives
the gradient all-reduce from the shardings."""
from __future__ import annotations

import functools

import jax
import numpy as np

from benchmark.correct import replica_checksums
from benchmark.trainers import Trainer, lm_objective, optimizer
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import lm_loss, make_train_step
from horovod_tpu.utils.compat import set_mesh


def _make(model, phase: dict, devices, seed: int):
    mesh = create_mesh(phase["mesh"], devices=devices)
    global_batch = phase["batch_per_chip"] * mesh.shape.get("dp", 1)
    example = np.zeros((global_batch, phase["seq"]), np.int32)
    rng = jax.random.PRNGKey(seed)
    init_fn, step_fn, _ = make_train_step(
        model, optimizer(), lm_loss, mesh=mesh)(rng, example)
    return global_batch, rng, init_fn, step_fn


def build(model, phase: dict, devices, seed: int) -> Trainer:
    global_batch, rng, init_fn, step_fn = _make(model, phase, devices, seed)
    batch_sharding = step_fn.shardings[1]
    return Trainer(
        global_batch=global_batch,
        init=functools.partial(init_fn, rng),
        step=step_fn,
        put=lambda ids: jax.device_put(ids, batch_sharding),
        params=lambda state: state.params,
        objective=lm_objective(model, lm_loss),
        checksums=lambda state: replica_checksums(state.params),
    )


def lower(model, phase: dict, devices) -> jax.stages.Lowered:
    """The step lowered for `devices` (described ones will do) at the
    phase's shape; the state's shapes come from `make_train_step`'s init
    unexecuted."""
    global_batch, rng, init_fn, step_fn = _make(model, phase, devices, seed=0)
    state_sh, batch_sh = step_fn.shardings
    ids = jax.ShapeDtypeStruct((global_batch, phase["seq"]), "int32",
                               sharding=batch_sh)
    with set_mesh(batch_sh.mesh):
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(init_fn.__wrapped__, rng), state_sh)
        return step_fn.__wrapped__.lower(state, ids)
