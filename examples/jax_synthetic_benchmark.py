"""Synthetic benchmark — the reference's headline measurement tool.

(ref: examples/pytorch_synthetic_benchmark.py — same CLI shape, prints
`Img/sec per chip` and `Total img/sec on N chip(s)`.) The step is one
jitted SPMD program over the dp mesh: XLA fuses the gradient psums into
the backward pass on ICI.

    python examples/jax_synthetic_benchmark.py --model resnet50
    python examples/jax_synthetic_benchmark.py --model gpt2-small --batch-size 8
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import time

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-chip batch size")
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--image-size", type=int, default=224)
    args = p.parse_args()

    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.train import (
        lm_loss,
        make_train_step,
        softmax_xent,
    )

    enable_compile_cache()
    hvd.init()
    n = len(jax.devices())
    mesh = create_mesh({"dp": n})
    spec = get_model(args.model)
    model = spec.make_model()

    global_batch = args.batch_size * n
    batch = spec.make_batch(global_batch)
    is_image = spec.kind == "image"
    rng = np.random.RandomState(0)
    if is_image:
        labels = rng.randint(0, 1000, (global_batch,), dtype=np.int32)
        batch = (batch[0], labels)
        loss_fn = softmax_xent
        has_bn = args.model.startswith("resnet")
    else:
        loss_fn = lm_loss
        has_bn = False

    build = make_train_step(
        model, optax.sgd(0.01, momentum=0.9), loss_fn, mesh=mesh,
        has_batch_stats=has_bn,
    )
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), *batch)
    state = init_fn(jax.random.PRNGKey(0))

    from jax.sharding import NamedSharding, PartitionSpec as P

    batch = tuple(
        jax.device_put(b, NamedSharding(mesh, P("dp"))) for b in batch
    )

    def run_batches(state, k):
        for _ in range(k):
            state, loss = step_fn(state, *batch)
        jax.device_get(loss)
        return state

    state = run_batches(state, args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        state = run_batches(state, args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        ips = global_batch * args.num_batches_per_iter / dt
        img_secs.append(ips / n)
        print(f"Iter #{i}: {ips:.1f} img/sec total")

    mean, std = np.mean(img_secs), 1.96 * np.std(img_secs)
    print(f"Img/sec per chip: {mean:.1f} +-{std:.1f}")
    print(f"Total img/sec on {n} chip(s): {mean * n:.1f} +-{std * n:.1f}")


if __name__ == "__main__":
    main()
