"""MNIST training — the framework's first-run example.

TPU-native port of the reference's first-run examples
(ref: examples/tensorflow2_mnist.py, examples/pytorch_mnist.py). Run:

    python examples/jax_mnist.py                 # mesh mode, all chips
    hvdrun -np 2 python examples/jax_mnist.py    # process mode, 2 ranks

Uses a synthetic MNIST-shaped dataset by default (no network egress);
pass --data-dir with the standard IDX files to train on real MNIST.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import gzip
import os
import struct

import numpy as np


def load_mnist(data_dir):
    """Standard IDX files (train-images-idx3-ubyte.gz etc.)."""
    def read_idx(path):
        with gzip.open(path, "rb") as f:
            magic, = struct.unpack(">I", f.read(4))
            ndim = magic & 0xFF
            dims = struct.unpack(f">{ndim}I", f.read(4 * ndim))
            return np.frombuffer(f.read(), np.uint8).reshape(dims)

    x = read_idx(os.path.join(data_dir, "train-images-idx3-ubyte.gz"))
    y = read_idx(os.path.join(data_dir, "train-labels-idx1-ubyte.gz"))
    return x.astype(np.float32) / 255.0, y.astype(np.int32)


def synthetic_mnist(n=8192, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    # Make it learnable: brighten a quadrant per class.
    for i in range(n):
        q = y[i] % 4
        r, c = divmod(q, 2)
        x[i, r * 14:(r + 1) * 14, c * 14:(c + 1) * 14] += y[i] / 10.0
    return x, y


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data-dir", default=None)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.001)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.models import MnistCNN

    enable_compile_cache()
    hvd.init()

    x, y = (load_mnist(args.data_dir) if args.data_dir
            else synthetic_mnist())
    # Shard the dataset across ranks the way the reference's
    # DistributedSampler does (examples/pytorch_mnist.py).
    n_shards = hvd.size() if hvd.mode() == "process" else 1
    shard = hvd.rank() if hvd.mode() == "process" else 0
    x, y = x[shard::n_shards], y[shard::n_shards]

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), x[: args.batch_size])

    # Scale LR by world size (linear-scaling rule the reference
    # documents, README.rst:91).
    tx = hvd.DistributedOptimizer(optax.adam(args.lr * hvd.size()))
    opt_state = tx.init(params)

    # Start ranks from identical weights (ref: broadcast_parameters,
    # horovod/torch/functions.py:30).
    params = hvd.broadcast_parameters(params, root_rank=0)

    # Compute grads under jit; run the (allreducing) optimizer update
    # eagerly so the same script serves mesh mode AND process mode —
    # exactly how the reference's torch script computes grads on device
    # and lets hooks allreduce them (examples/pytorch_mnist.py). For the
    # fully-jitted SPMD path see jax_synthetic_benchmark.py / wrap_step.
    @jax.jit
    def grad_step(params, bx, by):
        def loss_fn(p):
            logits = model.apply(p, bx)
            onehot = jax.nn.one_hot(by, 10)
            return -jnp.mean(
                jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1)
            )

        return jax.value_and_grad(loss_fn)(params)

    steps_per_epoch = len(x) // args.batch_size
    for epoch in range(args.epochs):
        perm = np.random.RandomState(epoch).permutation(len(x))
        for i in range(steps_per_epoch):
            idx = perm[i * args.batch_size:(i + 1) * args.batch_size]
            loss, grads = grad_step(params, x[idx], y[idx])
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(loss):.4f}")

    if hvd.rank() == 0:
        logits = model.apply(params, x[:1024])
        acc = float(np.mean(np.argmax(logits, -1) == y[:1024]))
        print(f"train accuracy (first 1024): {acc:.3f}")


if __name__ == "__main__":
    main()
