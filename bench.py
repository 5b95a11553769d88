"""Headline benchmark: ResNet-50 synthetic data-parallel throughput.

TPU-native port of the reference's measurement tool
(ref: examples/pytorch_synthetic_benchmark.py:93-117 — ResNet-50,
synthetic ImageNet batches, prints img/sec per GPU and total). Metric of
record (BASELINE.json): images/sec/chip; `vs_baseline` compares against
the reference's per-GPU ResNet-50 number from the same methodology
(docs/benchmarks.rst:16-43, ~170 img/sec on P100s).

Beyond throughput this also reports, in the same JSON line:
  - `mfu`: achieved model FLOPs utilization for the ResNet-50 step —
    XLA's cost analysis of the compiled train step divided by the
    chip's peak bf16 FLOPs. ResNet-50 with BatchNorm is HBM-bandwidth
    bound on TPU (see docs/benchmarks.md for the profile/roofline
    analysis), so this sits near the memory roofline, not the MXU peak.
  - `transformer_mfu`: the same measurement on a compute-dense
    flagship (BERT-base, seq 128 — one of the reference's own headline
    workloads, docs/benchmarks.rst:44-61). This is the MXU-bound
    number: ≥0.5 on v5e.
  - `gpt2_mfu`/`gpt2_mfu_dense`/`gpt2_flash_speedup`: the flagship
    GPT-2-small seq-2048 step, flash (Pallas) vs XLA dense at the SAME
    shape; `gpt2_long_mfu` at seq 4096 where dense cannot run
    (`gpt2_long_flops` labels the FLOP-numerator methodology).
  - `fused_bn_step_ms`/`fused_bn_delta_ms`: the ResNet step with the
    Pallas fused-BN kernel wired into stage 2 — keeps the wire-or-not
    question answered by a fresh measurement (docs/benchmarks.md).
  - `scaling_efficiency`: sharding-overhead efficiency, the north-star
    "allreduce scaling efficiency 1->N" trend (docs/benchmarks.rst:11-14
    measures 90% for ResNet on 512 GPUs). On a single host this is
    measured on an 8-virtual-device CPU mesh as t(1 device, batch B) /
    t(8 devices, same B): identical total compute on the same silicon,
    so any drop is the cost the GSPMD collectives add. Median over
    `--scaling-reps` order-statistic-paired probe samples;
    `scaling_spread` is the (max-min)/median across them and
    `scaling_samples` carries the raw per-rep seconds (+ the
    index-paired spread) for diagnosis. With >=2 real chips visible, a
    true weak-scaling sweep runs instead.

The training loop is a `lax.scan` over steps inside one jit (chunked),
so steps dispatch on-device back-to-back with no host round-trip
between them — the TPU-native shape of the reference's tight benchmark
loop (host dispatch gaps cost ~7% at ResNet-50 step times).

Prints ONE JSON line: {"metric","value","unit","vs_baseline",...}.
"""
from __future__ import annotations

import argparse
import json
import os
import functools
import statistics
import subprocess
import sys
import time


# Reference per-GPU ResNet-50 throughput implied by docs/benchmarks.rst
# (tf_cnn_benchmarks on 25GbE P100 clusters, ~170 img/sec/GPU).
BASELINE_IMG_SEC_PER_CHIP = 170.0

# Peak dense bf16 FLOP/s per chip, keyed by `device_kind` exactly as
# jax reports it (public figures: Google Cloud TPU documentation).
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


def _peak_flops(device) -> float:
    """A device kind that is not in the table is an error, not a
    default: an MFU against the wrong peak is worse than none."""
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py: unknown device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add its peak bf16 FLOP/s "
            f"to PEAK_FLOPS — known: {sorted(PEAK_FLOPS)}") from None


def _force_cpu(n_devices: int):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def _build(model_name, n_chips, batch_per_chip, image_size=224, mesh=None,
           donate=True, model_kw=None, seq_len=None, zero=False):
    import jax
    import numpy as np
    import optax

    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.train import (
        lm_loss,
        make_train_step,
        softmax_xent,
    )

    if mesh is None:
        # The first n_chips devices: the single-chip measurements run on
        # one chip of a multi-chip host (a {"dp": 1} mesh cannot cover
        # four devices).
        mesh = create_mesh({"dp": n_chips},
                           devices=jax.devices()[:n_chips])
    spec = get_model(model_name)
    model_kw = dict(model_kw or {})
    if spec.kind in ("lm", "encoder"):
        # The bench path opts INTO bf16 logits (the measured config:
        # 6.0 ms of a 98 ms GPT-2 step on v5e, docs/benchmarks.md r5).
        # The library default stays f32 — external logits consumers
        # keep full precision unless they ask otherwise (ADVICE r14).
        import jax.numpy as jnp

        model_kw.setdefault("logits_dtype", jnp.bfloat16)
    model = spec.make_model(**model_kw)
    rng = np.random.RandomState(42)
    global_batch = batch_per_chip * n_chips
    if spec.kind == "image":
        inputs = rng.rand(global_batch, image_size, image_size, 3).astype(
            np.float32
        )
        labels = rng.randint(0, 1000, size=(global_batch,), dtype=np.int32)
        loss_fn = softmax_xent
        tx = optax.sgd(0.01, momentum=0.9)
        has_bn = True
    else:  # lm / encoder: next-token loss over synthetic ids
        bkw = {} if seq_len is None else {"seq_len": seq_len}
        inputs = spec.make_batch(global_batch, **bkw)[0]
        labels = inputs
        loss_fn = lm_loss
        tx = optax.adamw(1e-4)
        has_bn = False

    build = make_train_step(
        model, tx, loss_fn, mesh=mesh, has_batch_stats=has_bn,
        donate=donate, zero=zero,
    )
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), inputs, labels)
    state = init_fn(jax.random.PRNGKey(0))

    from jax.sharding import NamedSharding, PartitionSpec as P

    dsh = NamedSharding(mesh, P(mesh.axis_names[0]))
    inputs = jax.device_put(inputs, dsh)
    labels = jax.device_put(labels, dsh)
    return state, step_fn, inputs, labels, global_batch, mesh


def _make_scan_step(step_fn, mesh, chunk: int):
    """One jit running `chunk` train steps back-to-back via lax.scan.

    Removes the per-step host dispatch gap (the device otherwise idles
    ~5-10ms between steps waiting for the next enqueue over the device
    transport)."""
    import jax

    inner = getattr(step_fn, "raw", None) or getattr(
        step_fn, "__wrapped__", step_fn)
    shardings = getattr(step_fn, "shardings", None)
    kw = {}
    if shardings is not None:
        kw = {"in_shardings": shardings,
              "out_shardings": (shardings[0], None),
              "donate_argnums": (0,)}

    @functools.partial(jax.jit, **kw)
    def multi(state, inputs, labels):
        def body(s, _):
            s, loss = inner(s, inputs, labels)
            return s, loss

        return jax.lax.scan(body, state, None, length=chunk)

    def run(state, inputs, labels):
        from horovod_tpu.utils.compat import set_mesh as _set_mesh
        with _set_mesh(mesh):
            return multi(state, inputs, labels)

    return run


def _time_scan(state, scan_fn, inputs, labels, chunk, chunks, warmup=1):
    import jax

    for _ in range(warmup):
        state, losses = scan_fn(state, inputs, labels)
    jax.block_until_ready(losses)

    t0 = time.perf_counter()
    for _ in range(chunks):
        state, losses = scan_fn(state, inputs, labels)
    jax.block_until_ready(losses)
    return (time.perf_counter() - t0) / (chunk * chunks), state


def _step_flops(step_fn, state, inputs, labels):
    """Per-step FLOPs from XLA's cost analysis of the compiled step
    (`step_fn` is make_train_step's wrapper around the jitted step)."""
    compiled = step_fn.__wrapped__.lower(state, inputs, labels).compile()
    return float(compiled.cost_analysis()["flops"])


def _out_of_memory(exc: BaseException) -> bool:
    """XLA's RESOURCE_EXHAUSTED: the one failure a batch-size sweep and
    the dense S=4096 compile are expected to meet on a 16 GB chip."""
    import jax

    return (isinstance(exc, jax.errors.JaxRuntimeError)
            and "RESOURCE_EXHAUSTED" in str(exc))


def _measure_mfu(model, batch, peak, image_size=224, chunk=8, chunks=2):
    """Steps/sec + cost-analysis MFU for one model on the real chip."""
    import jax

    state, step_fn, inputs, labels, global_batch, mesh = _build(
        model, 1, batch, image_size
    )
    scan_fn = _make_scan_step(step_fn, mesh, chunk)
    dt, state = _time_scan(state, scan_fn, inputs, labels, chunk, chunks)
    flops = _step_flops(step_fn, state, inputs, labels)
    return dt, global_batch, (flops / dt) / peak


def _measure_gpt2(peak, seq=2048, batch=4, chunk=12, chunks=2):
    """Long-sequence GPT-2 MFU headline: flash (Pallas) vs XLA dense at
    the SAME shape, so the kernel's contribution is a printed delta
    (ref methodology: docs/benchmarks.rst:16-43 — measure the flagship
    at its working sequence length, not a toy one).

    Model FLOPs for BOTH numbers come from the DENSE compiled step's
    cost analysis: the two implementations compute the same math, and
    counting the flash kernel's internal bwd recompute would inflate
    its own MFU (standard MFU methodology charges model FLOPs only).
    """
    times = {}
    flops = None
    for impl in ("dense", "flash"):
        state, step_fn, inputs, labels, _, mesh = _build(
            "gpt2-small", 1, batch,
            model_kw={"attn_impl": impl, "max_len": seq}, seq_len=seq,
        )
        scan_fn = _make_scan_step(step_fn, mesh, chunk)
        dt, state = _time_scan(state, scan_fn, inputs, labels, chunk,
                               chunks)
        if impl == "dense":
            flops = _step_flops(step_fn, state, inputs, labels)
        times[impl] = dt
        # Release this impl's train state before building the next one:
        # two full param+AdamW states resident at once can OOM shapes
        # each impl fits individually.
        del state, step_fn, scan_fn, inputs, labels
    return {
        "gpt2_mfu": round((flops / times["flash"]) / peak, 4),
        "gpt2_mfu_dense": round((flops / times["dense"]) / peak, 4),
        "gpt2_model": "gpt2-small",
        "gpt2_seq": seq,
        "gpt2_flash_speedup": round(times["dense"] / times["flash"], 3),
    }


def _measure_gpt2_long(peak, seq=4096, batch=4, chunk=8, chunks=2):
    """Long-context headline: GPT-2 at a sequence length where the
    DENSE step cannot even fit on the chip (the materialized attention
    probabilities alone exceed HBM) but the flash path trains. Model
    FLOPs still come from the dense program's cost analysis —
    lower().compile() never executes, so the infeasible-to-RUN dense
    step still yields the honest FLOP count; if even compilation
    refuses, the count is recovered analytically from two smaller
    dense compiles (model flops are exactly a*S + b*S^2 in sequence
    length at fixed batch)."""
    state, step_fn, inputs, labels, _, mesh = _build(
        "gpt2-small", 1, batch,
        model_kw={"attn_impl": "flash", "max_len": seq}, seq_len=seq,
    )
    scan_fn = _make_scan_step(step_fn, mesh, chunk)
    dt, state = _time_scan(state, scan_fn, inputs, labels, chunk, chunks)
    del state, step_fn, scan_fn, inputs, labels

    def dense_flops(s):
        st, fn, ins, lbs, _, _m = _build(
            "gpt2-small", 1, batch,
            model_kw={"attn_impl": "dense", "max_len": s}, seq_len=s,
        )
        fl = _step_flops(fn, st, ins, lbs)
        del st, fn, ins, lbs
        return fl

    flops_method = "dense-compile"
    try:
        flops = dense_flops(seq)
    except Exception as exc:
        if not _out_of_memory(exc):
            raise
        print(f"bench.py: dense seq-{seq} step does not fit the chip "
              f"({str(exc).splitlines()[0]}); extrapolating its FLOPs",
              file=sys.stderr)
        s1, s2 = seq // 4, seq // 2
        f1, f2 = dense_flops(s1), dense_flops(s2)
        # Solve f = a*s + b*s^2 through the two points.
        b = (f2 / s2 - f1 / s1) / (s2 - s1)
        a = f1 / s1 - b * s1
        flops = a * seq + b * seq * seq
        flops_method = "extrapolated-quadratic"
    return {
        "gpt2_long_mfu": round((flops / dt) / peak, 4),
        "gpt2_long_seq": seq,
        # Methodology label: dense-equivalent FLOPs (full S^2 attention
        # work, incl. the masked half the causal flash kernel skips),
        # and whether the dense count was compiled at this seq or fit
        # through two smaller dense compiles — so nobody quotes the
        # number as fully measured when it is extrapolated.
        "gpt2_long_flops": flops_method,
        "gpt2_long_mfu_convention": "dense-equivalent",
    }


def _scaling_probe(n_devices: int, batch: int, image_size: int,
                   iters: int, reps: int = 1):
    """Child-process entry: time `reps` independent samples of `iters`
    steps of a FIXED global batch on an n-device CPU mesh (one compile,
    reps cheap runs); print a seconds list on the last line.

    Plain per-step dispatch, not the scan loop: compiling a scan-of-
    steps ResNet-50 on this single CPU core takes several minutes,
    which would dwarf the signal. Per-call dispatch overhead is
    identical for both device counts, so the ratio stays a valid
    overhead trend (see module docstring).

    Every rep restarts from the SAME initial state (donation off): CPU
    per-step cost depends on the parameter trajectory (denormal-heavy
    regions run far slower), so timing a continuing trajectory makes
    reps incomparable — with a fixed start, every rep on every device
    count times the identical computation."""
    _force_cpu(n_devices)
    state0, step_fn, images, labels, _, mesh = _build(
        "resnet50", n_devices, batch // n_devices, image_size,
        donate=False,
    )
    # Warm with one full discarded rep (compile + first-touch paging),
    # then take comparable samples.
    import jax

    state = state0
    for _ in range(iters):
        state, loss = step_fn(state, images, labels)
    jax.block_until_ready(loss)
    samples = []
    for _ in range(reps):
        state = state0
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step_fn(state, images, labels)
        jax.block_until_ready(loss)
        samples.append(time.perf_counter() - t0)
    print(json.dumps({"seconds": samples}))


def _measure_scaling(batch=32, image_size=64, iters=8, reps=5):
    """t(1 dev)/t(8 dev) for the same global batch: one subprocess per
    device count (fresh backend), `reps` timed samples inside each (one
    compile per count). Returns (median-ratio, spread, samples); a
    probe child that fails or times out fails the run.

    Variance handling (r5, after the r4 spread regression to 0.089):
    per-rep samples within one process are independent replays of the
    identical computation, so their scatter is pure host noise — rep i
    of the 1-device run shares nothing with rep i of the 8-device run.
    Index-pairing those reps (r4) therefore MANUFACTURED ratio variance
    from unrelated noise draws. Pairing order statistics instead
    (sorted t1 against sorted t8) compares like against like — fastest
    clean sample to fastest, most-contended to most-contended — so the
    quoted spread reflects genuine between-sample disagreement, not
    pairing luck. The raw per-rep seconds for both device counts ride
    along in the JSON so a regression is diagnosable from the artifact
    (tight t1 + scattered t8 → collective/dispatch jitter; both lists
    drifting monotonically → host thermal/contention drift)."""
    times = {}
    for n in (1, 8):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--scaling-probe", str(n), "--batch-size", str(batch),
               "--image-size", str(image_size),
               "--num-iters", str(iters), "--scaling-reps", str(reps)]
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=1800,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"scaling probe ({n} devices) exited {out.returncode}:\n"
                f"{out.stderr[-2000:]}")
        times[n] = json.loads(
            out.stdout.strip().splitlines()[-1])["seconds"]
    ratios = [t1 / t8 for t1, t8 in zip(sorted(times[1]),
                                        sorted(times[8]))]
    med = statistics.median(ratios)
    spread = (max(ratios) - min(ratios)) / med if med else 0.0
    # Order-statistic pairing minimizes (max-min) over pairings, so the
    # primary spread is a LOWER bound on ratio uncertainty; the
    # r3/r4-comparable index-paired spread rides along so cross-round
    # trends (and one-sided per-count jitter it would catch) stay
    # visible.
    iratios = [t1 / t8 for t1, t8 in zip(times[1], times[8])]
    imed = statistics.median(iratios)
    ispread = (max(iratios) - min(iratios)) / imed if imed else 0.0
    samples = {"t1": [round(t, 4) for t in times[1]],
               "t8": [round(t, 4) for t in times[8]],
               "spread_indexpair": round(ispread, 3)}
    return med, spread, samples


def _real_weak_scaling(n_chips, model, batch_per_chip, image_size, iters):
    """True weak scaling on real chips: img/sec/chip at n vs at 1."""
    import jax
    from horovod_tpu.parallel.mesh import create_mesh

    per_chip = {}
    for n in (1, n_chips):
        devices = jax.devices()[:n]
        mesh = create_mesh({"dp": n}, devices=devices)
        state, step_fn, images, labels, global_batch, mesh = _build(
            model, n, batch_per_chip, image_size, mesh=mesh
        )
        scan_fn = _make_scan_step(step_fn, mesh, iters)
        dt, _ = _time_scan(state, scan_fn, images, labels, iters, 1)
        per_chip[n] = global_batch / dt / n
    return per_chip[n_chips] / per_chip[1]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=0,
                   help="per-chip batch; 0 = sweep {256,512} and keep best")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-iters", type=int, default=24,
                   help="total timed steps (scan chunks of 8)")
    p.add_argument("--cpu", action="store_true",
                   help="force CPU (tiny shapes) for smoke runs")
    p.add_argument("--no-scaling", action="store_true")
    p.add_argument("--no-transformer", action="store_true",
                   help="skip the BERT-base MFU measurement")
    p.add_argument("--no-fused-bn", action="store_true",
                   help="skip the fused-BN-wired ResNet step comparison")
    p.add_argument("--no-gpt2", action="store_true",
                   help="skip the long-sequence GPT-2 flash/dense MFU")
    p.add_argument("--gpt2-seq", type=int, default=2048)
    p.add_argument("--gpt2-batch", type=int, default=4)
    p.add_argument("--zero", action="store_true",
                   help="shard optimizer state over dp (GSPMD ZeRO; "
                        "docs/running.md 'ZeRO sharded optimizer state')")
    p.add_argument("--scaling-reps", type=int, default=5)
    p.add_argument("--scaling-probe", type=int, default=0,
                   help="internal: run the N-device CPU scaling probe")
    args = p.parse_args()

    if args.scaling_probe:
        _scaling_probe(args.scaling_probe, args.batch_size or 32,
                       args.image_size, args.num_iters, args.scaling_reps)
        return

    if args.cpu:
        _force_cpu(1)
        args.batch_size = min(args.batch_size or 16, 16)
        args.image_size = min(args.image_size, 64)
        args.num_iters = min(args.num_iters, 4)

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    device = jax.devices()[0]
    n_chips = len(jax.devices())
    if args.cpu:
        peak = None  # no device metric is computed from a CPU run
    elif device.platform != "tpu":
        raise SystemExit(
            f"bench.py: platform is {device.platform!r}, not 'tpu' — a "
            "device benchmark does not fall back to another platform; "
            "pass --cpu for the tiny CPU smoke")
    else:
        peak = _peak_flops(device)

    chunk = max(min(args.num_iters // 3, 8), 1)
    candidates = [args.batch_size] if args.batch_size else [256, 512]
    best = None
    for bs in candidates:
        try:
            state, step_fn, images, labels, global_batch, mesh = _build(
                args.model, n_chips, bs, args.image_size, zero=args.zero
            )
            scan_fn = _make_scan_step(step_fn, mesh, chunk)
            # Short probe decides the sweep; two chunks, not one — a
            # single-chunk probe has occasionally crowned the slower
            # batch size on scheduler noise. The winner gets the full
            # run.
            dt, state = _time_scan(state, scan_fn, images, labels, chunk, 2)
            rate = global_batch / dt
        except Exception as exc:
            # A sweep candidate too large for the chip is an answer;
            # anything else that breaks fails the run.
            if not _out_of_memory(exc) or len(candidates) == 1:
                raise
            print(f"bench.py: batch {bs} does not fit the chip "
                  f"({str(exc).splitlines()[0]})", file=sys.stderr)
            continue
        if best is None or rate > best[1]:
            best = (bs, rate, state, step_fn, scan_fn, images, labels,
                    global_batch)
    if best is None:
        raise RuntimeError("no batch size compiled/ran successfully")
    (bs, _, state, step_fn, scan_fn, images, labels, global_batch) = best

    chunks = max(args.num_iters // chunk, 1)
    dt, state = _time_scan(state, scan_fn, images, labels, chunk, chunks)
    img_sec_total = global_batch / dt
    img_sec_chip = img_sec_total / n_chips

    flops = _step_flops(step_fn, state, images, labels)
    mfu = None
    if not args.cpu:
        # cost_analysis() reports the SPMD-partitioned (per-device)
        # module, so this is per-chip utilization already — no division
        # by chip count.
        mfu = (flops / dt) / peak

    fused_bn_ms = None
    if (args.model == "resnet50" and not args.cpu
            and not args.no_fused_bn):
        # End-to-end measurement of the Pallas fused BN+ReLU+1x1 kernel
        # wired into stage 2 (the shape where it beats XLA 1.36x in
        # isolation, docs/kernels.md) — the r5 answer to "would wiring
        # it in actually move the step?" (docs/benchmarks.md).
        fstate, fstep, fim, flb, _, fmesh = _build(
            args.model, n_chips, bs, args.image_size,
            model_kw={"fuse_bn_conv_stages": (1,)},
        )
        fscan = _make_scan_step(fstep, fmesh, chunk)
        fdt, _ = _time_scan(fstate, fscan, fim, flb, chunk, chunks)
        fused_bn_ms = fdt * 1e3
        del fstate, fstep, fscan, fim, flb

    tr_mfu = None
    if not (args.no_transformer or args.cpu):
        _, _, tr_mfu = _measure_mfu("bert-base", 256, peak)

    gpt2 = None
    if not (args.no_gpt2 or args.cpu):
        gpt2 = {
            **_measure_gpt2(peak, seq=args.gpt2_seq,
                            batch=args.gpt2_batch),
            **_measure_gpt2_long(peak),
        }

    scaling = spread = scaling_samples = None
    if args.no_scaling or args.cpu:
        pass
    elif n_chips > 1:
        scaling = _real_weak_scaling(n_chips, args.model, bs,
                                     args.image_size,
                                     max(args.num_iters // 2, 1))
    else:
        scaling, spread, scaling_samples = _measure_scaling(
            reps=args.scaling_reps)

    result = {
        "metric": f"{args.model}_synthetic_img_sec_per_chip",
        "value": round(img_sec_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_sec_chip / BASELINE_IMG_SEC_PER_CHIP, 3),
        "batch_per_chip": bs,
        "n_chips": n_chips,
        "device": {"platform": device.platform,
                   "kind": device.device_kind, "count": n_chips},
    }
    if args.zero:
        result["zero"] = True
    if mfu is not None:
        result["mfu"] = round(mfu, 4)
    if fused_bn_ms is not None:
        # Positive delta = fused kernel made the step faster.
        result["fused_bn_step_ms"] = round(fused_bn_ms, 2)
        result["fused_bn_delta_ms"] = round(dt * 1e3 - fused_bn_ms, 2)
    if tr_mfu is not None:
        result["transformer_mfu"] = round(tr_mfu, 4)
        result["transformer_model"] = "bert-base"
    if gpt2 is not None:
        result.update(gpt2)
    if scaling is not None:
        result["scaling_efficiency"] = round(scaling, 3)
        result["scaling_mode"] = ("weak_real" if n_chips > 1
                                  else "overhead_cpu8")
        if spread is not None:
            result["scaling_spread"] = round(spread, 3)
        if scaling_samples is not None:
            result["scaling_samples"] = scaling_samples
    print(json.dumps(result))


if __name__ == "__main__":
    main()
