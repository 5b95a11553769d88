"""GPT-2 training across every parallelism axis the framework offers.

The reference's north-star LM config is "GPT-2 1.3B + Adasum"
(BASELINE.json); this script trains any registry GPT-2 size over a
configurable pp x dp x ep x sp x tp mesh with ring/Ulysses attention and
optional MoE — capabilities beyond the reference's DP-only scope
(SURVEY.md §2.6).

    python examples/jax_gpt2_train.py --model gpt2-small --dp 4 --tp 2
    python examples/jax_gpt2_train.py --model gpt2-1p3b --dp 8 --tp 4 \
        --sp 2 --attn ring --remat
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import time

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2-small")
    p.add_argument("--batch-size", type=int, default=8, help="global batch")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--attn", default="dense",
                   choices=["dense", "ring", "ulysses", "flash"])
    p.add_argument("--n-experts", type=int, default=0)
    p.add_argument("--remat", action="store_true")
    args = p.parse_args()

    import dataclasses

    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.models.pipelined import PipelinedLM
    from horovod_tpu.models.transformer import GPT2_CONFIGS, TransformerLM
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.sharding import DEFAULT_RULES, PIPELINE_RULES
    from horovod_tpu.parallel.train import lm_loss, make_train_step

    enable_compile_cache()
    hvd.init()
    axes = {k: v for k, v in
            [("pp", args.pp), ("dp", args.dp), ("ep", args.ep),
             ("sp", args.sp), ("tp", args.tp)]}
    mesh = create_mesh(axes)

    cfg = GPT2_CONFIGS[args.model]
    cfg = dataclasses.replace(
        cfg,
        max_len=max(cfg.max_len, args.seq_len),
        attn_impl=args.attn,
        remat=args.remat,
        n_experts=args.n_experts,
        scan_layers=args.pp > 1,
        # Training path: bf16 logits (the measured config — lm_loss
        # upcasts to f32 inside its softmax, so only the lm-head HBM
        # traffic changes; the library default stays f32, ADVICE r14).
        logits_dtype=jax.numpy.bfloat16,
    )
    if args.pp > 1:
        model = PipelinedLM(cfg, mesh)
        rules = PIPELINE_RULES
    else:
        model = TransformerLM(cfg)
        rules = DEFAULT_RULES

    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (args.batch_size, args.seq_len), dtype=np.int32
    )

    tx = optax.adamw(args.lr)
    build = make_train_step(
        model, tx, lm_loss, mesh=mesh, rules=rules, shard_seq=args.sp > 1,
        moe_aux_weight=0.01 if args.n_experts else 0.0,
    )
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), ids)
    state = init_fn(jax.random.PRNGKey(0))

    for i in range(args.steps):
        t0 = time.perf_counter()
        state, loss = step_fn(state, ids)
        loss = float(loss)
        if hvd.rank() == 0:
            dt = time.perf_counter() - t0
            toks = args.batch_size * args.seq_len / dt
            print(f"step {i}: loss={loss:.4f}  {toks:,.0f} tokens/sec")


if __name__ == "__main__":
    main()
