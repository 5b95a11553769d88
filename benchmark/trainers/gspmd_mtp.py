"""The GSPMD trainer of a model with a multi-token-prediction module:
`create_mesh` -> `make_train_step(..., aux_loss_fn=mtp_loss(weight))`.
The step differentiates `lm_loss` of the model's logits plus `weight` x
the cross-entropy of the logits the module sowed, two tokens on; the
objective below is made of the same two functions over the same
collection, each restricted to the first n positions. The weight is the
configuration's (DeepSeek-V3 report section 4.2, first phase: 0.3;
`mtp_loss_weight` in configs/latent_moe/joyai-llm-flash.json says why)."""
from __future__ import annotations

import functools

import jax
import numpy as np

from benchmark.correct import replica_checksums
from benchmark.trainers import CHOICES, Trainer, optimizer
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import (
    AUX_COLLECTION, lm_loss, make_train_step, mtp_loss)
from horovod_tpu.utils.compat import set_mesh

MTP_WEIGHT = 0.3
SECOND_TERM = mtp_loss(MTP_WEIGHT)


def _make(model, phase: dict, devices, seed: int):
    mesh = create_mesh(phase["mesh"], devices=devices)
    global_batch = phase["batch_per_chip"] * mesh.shape.get("dp", 1)
    example = np.zeros((global_batch, phase["seq"]), np.int32)
    rng = jax.random.PRNGKey(seed)
    init_fn, step_fn, _ = make_train_step(
        model, optimizer(), lm_loss, mesh=mesh, aux_loss_fn=SECOND_TERM)(
            rng, example)
    return global_batch, rng, init_fn, step_fn


def objective(model):
    def two_terms(params, ids, n):
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=[CHOICES, AUX_COLLECTION])
        seen = jax.tree.map(lambda a: a[:, :n], sown[AUX_COLLECTION])
        loss = (lm_loss(logits[:, :n], ids[:, :n])
                + SECOND_TERM(seen, ids[:, :n]))
        return loss, logits, sown.get(CHOICES, {})

    return two_terms


def build(model, phase: dict, devices, seed: int) -> Trainer:
    global_batch, rng, init_fn, step_fn = _make(model, phase, devices, seed)
    batch_sharding = step_fn.shardings[1]
    return Trainer(
        global_batch=global_batch,
        init=functools.partial(init_fn, rng),
        step=step_fn,
        put=lambda ids: jax.device_put(ids, batch_sharding),
        params=lambda state: state.params,
        objective=objective(model),
        checksums=lambda state: replica_checksums(state.params),
    )


def lower(model, phase: dict, devices) -> jax.stages.Lowered:
    """The step lowered for `devices` from shapes alone, as
    `trainers/gspmd.py::lower` does it."""
    global_batch, rng, init_fn, step_fn = _make(model, phase, devices, seed=0)
    state_sh, batch_sh = step_fn.shardings
    ids = jax.ShapeDtypeStruct((global_batch, phase["seq"]), "int32",
                               sharding=batch_sh)
    with set_mesh(batch_sh.mesh):
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(init_fn.__wrapped__, rng), state_sh)
        return step_fn.__wrapped__.lower(state, ids)
