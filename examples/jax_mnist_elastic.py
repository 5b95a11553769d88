"""Elastic MNIST — fault-tolerant training with dynamic hosts.

(ref: examples/elastic/pytorch_mnist_elastic.py.) Run with a discovery
script that prints the current `host[:slots]` set:

    hvdrun -np 2 --min-np 1 --max-np 4 \
        --host-discovery-script ./discover_hosts.sh \
        python examples/jax_mnist_elastic.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.001)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.elastic.state import JaxState
    from horovod_tpu.models import MnistCNN

    enable_compile_cache()
    hvd.init()

    from jax_mnist import synthetic_mnist

    x, y = synthetic_mnist()
    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), x[: args.batch_size])
    tx = hvd.DistributedOptimizer(optax.adam(args.lr * hvd.size()))

    @jax.jit
    def grad_step(params, bx, by):
        def loss_fn(p):
            logits = model.apply(p, bx)
            onehot = jax.nn.one_hot(by, 10)
            return -jnp.mean(
                jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1)
            )

        return jax.value_and_grad(loss_fn)(params)

    state = JaxState(
        params=params, opt_state=tx.init(params), epoch=0, batch=0
    )

    @hvd.elastic.run
    def train(state):
        steps = len(x) // args.batch_size
        while state.epoch < args.epochs:
            # Re-shard data for the *current* world each epoch.
            xs = x[hvd.rank()::hvd.size()]
            ys = y[hvd.rank()::hvd.size()]
            while state.batch < steps // hvd.size():
                lo = state.batch * args.batch_size
                bx = xs[lo:lo + args.batch_size]
                by = ys[lo:lo + args.batch_size]
                if len(bx) == 0:
                    break
                loss, grads = grad_step(state.params, bx, by)
                upd, state.opt_state = tx.update(
                    grads, state.opt_state, state.params
                )
                import optax as _optax

                state.params = _optax.apply_updates(state.params, upd)
                state.batch += 1
                if state.batch % 10 == 0:
                    state.commit()
            if hvd.rank() == 0:
                print(f"epoch {state.epoch}: loss={float(loss):.4f} "
                      f"(world size {hvd.size()})")
            state.epoch += 1
            state.batch = 0
            state.commit()

    train(state)


if __name__ == "__main__":
    main()
