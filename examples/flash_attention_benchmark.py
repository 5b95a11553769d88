"""Flash-attention kernel benchmark: Pallas vs XLA dense, forward and
forward+backward, across sequence lengths (the numbers quoted in
docs/kernels.md come from this script on one v5e chip).

Run:  python examples/flash_attention_benchmark.py [--dtype bf16]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
import time

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring import dense_attention
from horovod_tpu.utils.compile_cache import enable_compile_cache


def bench(fn, args, iters=20):
    out = fn(*args)
    first = out[0] if isinstance(out, tuple) else out
    jax.device_get(np.asarray(first).ravel()[:1])
    best = float("inf")
    for _ in range(2):  # two rounds; first can hit warmup anomalies
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        first = out[0] if isinstance(out, tuple) else out
        jax.device_get(np.asarray(first).ravel()[:1])
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)
    return best


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()
    enable_compile_cache()
    dt = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    H, D = args.heads, args.head_dim
    rng = np.random.RandomState(0)

    print(f"platform={jax.devices()[0].platform} "
          f"dtype={args.dtype} H={H} D={D}")
    print(f"{'B':>3} {'S':>6} | {'fwd flash':>9} {'fwd dense':>9} "
          f"{'x':>5} | {'f+b flash':>9} {'f+b dense':>9} {'x':>5}  (ms)")
    for B, S in [(8, 512), (4, 1024), (2, 2048), (2, 4096), (1, 8192)]:
        q, k, v = (jnp.asarray(rng.randn(B, S, H, D), dt)
                   for _ in range(3))
        f_fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                        causal=True))
        d_fwd = jax.jit(lambda q, k, v: dense_attention(q, k, v,
                                                        causal=True))
        f_g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)
            ** 2), argnums=(0, 1, 2)))
        d_g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            dense_attention(q, k, v, causal=True).astype(jnp.float32)
            ** 2), argnums=(0, 1, 2)))
        tf_, td = bench(f_fwd, (q, k, v), args.iters), \
            bench(d_fwd, (q, k, v), args.iters)
        gf, gd = bench(f_g, (q, k, v), args.iters), \
            bench(d_g, (q, k, v), args.iters)
        print(f"{B:>3} {S:>6} | {tf_:>9.2f} {td:>9.2f} {td / tf_:>5.2f} "
              f"| {gf:>9.2f} {gd:>9.2f} {gd / gf:>5.2f}")


if __name__ == "__main__":
    main()
