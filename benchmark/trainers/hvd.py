"""The README's spelling: `hvd.init`, `hvd.DistributedOptimizer` inside
`hvd.wrap_step` over `hvd.mesh()`, the user's own step function — the
lines of chip_smoke.py's phase (b). Gradients are all-reduced by the
optimizer wrapper, leaf by leaf."""
from __future__ import annotations

import flax.linen as nn
import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from benchmark.correct import replica_checksums
from benchmark.trainers import Trainer, lm_objective, optimizer
from horovod_tpu.parallel.train import lm_loss


def build(model, phase: dict, devices, seed: int) -> Trainer:
    hvd.shutdown()
    hvd.init(devices=list(devices))
    mesh, axis = hvd.mesh(), hvd.axis_name()
    if phase["mesh"] != {axis: len(devices)}:
        raise ValueError(
            f"the hvd trainer runs on hvd.mesh(), {{{axis!r}: "
            f"{len(devices)}}}; the traffic file asks for {phase['mesh']}")
    global_batch = phase["batch_per_chip"] * hvd.size()
    example = np.zeros((global_batch, phase["seq"]), np.int32)
    rng = jax.random.PRNGKey(seed)
    tx = hvd.DistributedOptimizer(optimizer())

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, batch), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss))

    wrapped = hvd.wrap_step(train_step, mesh=mesh, replicated_argnums=(0, 1),
                            donate_argnums=(0, 1))
    replicated = NamedSharding(mesh, P())

    def make_state(key):
        params = nn.unbox(model.init(key, example))["params"]
        return params, tx.init(params)

    init = jax.jit(make_state, out_shardings=replicated)

    def step(state, batch):
        params, opt_state, loss = wrapped(*state, batch)
        return (params, opt_state), loss

    batch_sharding = NamedSharding(mesh, P(axis))
    return Trainer(
        global_batch=global_batch,
        init=lambda: init(rng),
        step=step,
        put=lambda ids: jax.device_put(ids, batch_sharding),
        params=lambda state: state[0],
        objective=lm_objective(model, lm_loss),
        checksums=lambda state: replica_checksums(state[0]),
        close=hvd.shutdown,
    )
