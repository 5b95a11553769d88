"""Adasum gradient aggregation demo (ref: examples/adasum_small_model.py
and the GPT-2+Adasum north-star config in BASELINE.json).

Adasum combines gradients scaling-insensitively: for orthogonal
gradients it sums, for parallel ones it averages — so the effective LR
doesn't need the 1/N rescale of plain averaging (ref:
horovod/common/ops/adasum/adasum.h). Two spellings:

  * traced: `hvd.allreduce(g, op=hvd.Adasum)` inside shard_map lowers to
    the ppermute ladder in ops/adasum.py;
  * eager (process mode): the engine routes ADASUM requests through the
    native C++ VHDD kernel (horovod_tpu/cc/core.cc).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def main():
    import functools

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.utils.compat import shard_map

    enable_compile_cache()
    hvd.init()

    if hvd.mode() == "process":
        # Eager path through the engine (power-of-2 world required).
        g = np.ones(8, np.float32) * (hvd.rank() + 1)
        out = hvd.allreduce(g, op=hvd.Adasum, name="grad")
        print(f"rank {hvd.rank()}: adasum -> {out[:3]}")
        return

    # Mesh mode: Adasum inside one SPMD step.
    mesh = hvd.mesh()
    axis = hvd.axis_name()
    n = mesh.size

    def per_chip_step(w, x, y):
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)

        l, g = jax.value_and_grad(loss)(w)
        g = hvd.allreduce(g, op=hvd.Adasum, axis_name=axis)
        return w - 0.1 * g, hvd.allreduce(l, axis_name=axis)

    rng = np.random.RandomState(0)
    W = jnp.zeros((4, 1))
    X = rng.randn(8 * n, 4).astype(np.float32)
    Y = (X @ rng.randn(4, 1)).astype(np.float32)

    step = jax.jit(shard_map(
        per_chip_step, mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P()),
    ))
    for i in range(20):
        W, loss = step(W, X, Y)
    print(f"adasum-trained loss after 20 steps: {float(loss):.6f}")


if __name__ == "__main__":
    main()
