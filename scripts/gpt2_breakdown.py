"""Per-component ms breakdown of the flagship GPT-2 seq-2048 flash
train step (the r5 analogue of the r4 ResNet ms-by-ms closure,
docs/benchmarks.md:53-94; ref methodology: docs/benchmarks.rst:16-43).

Times ablation variants of the SAME step on the real chip so each
subtraction isolates one component:

  full            flash step, lm_loss (the bench headline step)
  loss_mean       xent replaced by mean(logits): full - this = softmax
                  cross-entropy cost (fwd softmax + bwd dlogits forming)
  tiny_vocab      vocab 512: full - this ~= the whole lm-head region
                  (logits matmul fwd + 2 bwd matmuls + loss at V=50257)

The AdamW share has no ablation (removing the update changes the
program globally); it is bounded analytically in docs/benchmarks.md.
For bucket-level attribution use jax.profiler.trace around one scan
chunk and aggregate the device lane — the r5 profile tables in
docs/benchmarks.md were produced that way.

Usage: python scripts/gpt2_breakdown.py [--seq 2048] [--batch 4]
Prints one JSON line per variant plus the subtraction table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_variant(loss_kind, vocab, seq, batch, attn="flash"):
    import jax
    import optax

    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.train import lm_loss, make_train_step

    mesh = create_mesh({"dp": 1})
    spec = get_model("gpt2-small")
    kw = {"attn_impl": attn, "max_len": seq}
    if vocab is not None:
        kw["vocab_size"] = vocab
    model = spec.make_model(**kw)
    rng = np.random.RandomState(42)
    ids = rng.randint(0, vocab or 50257, size=(batch, seq), dtype=np.int32)

    if loss_kind == "xent":
        loss_fn = lm_loss
    elif loss_kind == "mean":
        def loss_fn(logits, ids):
            import jax.numpy as jnp

            return jnp.mean(logits.astype(jnp.float32))
    else:
        raise ValueError(loss_kind)

    build = make_train_step(model, optax.adamw(1e-4), loss_fn, mesh=mesh)
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), ids, ids)
    state = init_fn(jax.random.PRNGKey(0))
    return state, step_fn, ids, mesh


def time_variant(name, loss_kind, vocab, seq, batch, chunk, chunks,
                 attn="flash"):
    from bench import _make_scan_step, _step_flops, _time_scan

    state, step_fn, ids, mesh = build_variant(
        loss_kind, vocab, seq, batch, attn)
    scan_fn = _make_scan_step(step_fn, mesh, chunk)
    dt, state = _time_scan(state, scan_fn, ids, ids, chunk, chunks)
    flops = _step_flops(step_fn, state, ids, ids)
    del state, step_fn, scan_fn
    rec = {"variant": name, "ms": round(dt * 1e3, 2),
           "tflops_counted": round((flops or 0) / 1e12, 3)}
    print(json.dumps(rec), flush=True)
    return dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--chunks", type=int, default=1)
    args = ap.parse_args()

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    S, B, C, N = args.seq, args.batch, args.chunk, args.chunks
    full = time_variant("full", "xent", None, S, B, C, N)
    mean = time_variant("loss_mean", "mean", None, S, B, C, N)
    tiny = time_variant("tiny_vocab", "xent", 512, S, B, C, N)

    print(json.dumps({
        "xent_cost_ms": round((full - mean) * 1e3, 2),
        "lm_head_region_ms": round((full - tiny) * 1e3, 2),
        "full_ms": round(full * 1e3, 2),
    }), flush=True)


if __name__ == "__main__":
    main()
