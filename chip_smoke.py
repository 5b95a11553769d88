#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the trainer's main path once on whatever TPU chips this machine
has, through the entry points a user calls, at the published width of
`gpt2-small` (12 layers, d_model 768, 12 heads, vocab 50257), seq 2048,
batch 4 per chip, `attn_impl="flash"`, bf16 logits, `optax.adamw` —
and checks what comes out. Weights are random, from a seed. Three
phases, one after another, each in a process that has exited before the
next starts (a chip belongs to one process at a time; this parent never
imports jax):

  kernel    flash_attention forward + gradients against the dense
            reference of parallel/ring.py, on the chip, at the shapes
            the benchmark runs (S=2048 and S=4096, causal
            bf16) and one padded non-causal case.
  train     (a) hvd.init → get_model → create_mesh({"dp": n}) →
            make_train_step, and (b) the README's lines —
            hvd.DistributedOptimizer inside hvd.wrap_step over
            hvd.mesh(). Each takes STEPS steps dispatched one by one on
            a fixed batch, each ending in block_until_ready: the loss is
            finite and lower at the end, step 0 of (a) and (b) agree
            from the same initial parameters, and the lowered step
            holds the Mosaic custom call (the compiled kernel ran, not
            the interpreter). With n > 1 chips: parameters and batch
            really are spread over all n, every chip holds memory after
            a step, the compiled step holds an all-reduce, and step 0
            agrees with the same global batch on a one-device mesh.
  launcher  `python -m horovod_tpu.runner.launch -np <chips>` with a
            worker that owns exactly one TPU device, a different chip
            per worker, computes gradients under jit on it, and updates
            through the host engine (built from cc/core.cc, never the
            NumPy fallback).

Exit code 0 only when every phase passed on a TPU, and then the last
line of stdout is the result, one JSON object with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as jax reported it. The stdout line before it,
`report: {...}`, carries the versions, the cache directory, compile
seconds, step milliseconds and memory — information for the reader,
not a claim. Any failed phase, a platform other than the TPU, or no
accelerator: non-zero, and nothing on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

MODEL = "gpt2-small"
SEQ = 2048
BATCH_PER_CHIP = 4
STEPS = 6
LEARNING_RATE = 1e-4

# Wall-clock caps in seconds; with process start-up they stay inside
# the 1200 s the contract allows, compilation included.
PHASE_SECONDS = {"kernel": 240, "train": 640, "launcher": 240}
TOTAL_SECONDS = 1150

# bf16 carries 8 bits of mantissa (eps 2^-8 = 0.0039). The kernel rounds
# the probabilities and its output to bf16 once each, so against an
# f32/highest-precision reference the error stays within a few eps of
# the largest value; an 8-bit float (eps 2^-4) or a dropped term would
# exceed it several times over.
KERNEL_TOL = 2e-2
# Step-0 losses come from the same parameters and batch through two
# programs that differ only in reduction order; one bf16 eps bounds it.
LOSS_TOL = 4e-3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def require_tpu():
    """The devices, or a failure: the TPU platform and a device kind
    the benchmark can measure (benchmark/peaks.py is the one table)."""
    import jax

    from benchmark import peaks

    devices = jax.devices()
    try:
        peaks.for_device(devices[0])
    except peaks.UnknownDevice as exc:
        check(False, str(exc))
    check(True, f"a TPU of kind {devices[0].device_kind!r}, which "
          "benchmark/peaks.py knows")
    return devices


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu")}


# ---------------------------------------------------------------- kernel

def kernel_case(S: int, causal: bool, padded: bool, B: int = 2, H: int = 4,
                D: int = 64) -> dict:
    """flash_attention forward and gradients vs the dense reference,
    bf16 inputs, on the default device. The reference takes the same
    bf16 values in f32 at the highest matmul precision (on a TPU an f32
    matmul is otherwise computed in bf16 passes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring import dense_attention

    rng = np.random.RandomState(S + 2 * causal + padded)
    q, k, v, w = (jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
                  for _ in range(4))
    mask = None
    if padded:
        # Row 0 keeps 3/4 of its keys, row 1 about half: trailing pads.
        lengths = np.array([S - S // 4, S // 2 + 1] * (B // 2))
        mask = jnp.asarray(np.arange(S)[None, :] < lengths[:, None],
                           jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, mask, causal=causal)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    def loss_ref(q, k, v):
        out = dense_attention(q, k, v, causal=causal, mask=mask)
        return jnp.sum(out * w.astype(jnp.float32)), out

    grad_flash = jax.jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2),
                                            has_aux=True))
    lowered = grad_flash.lower(q, k, v).as_text()
    check("tpu_custom_call" in lowered,
          f"S={S}: the lowered kernel is a Mosaic tpu_custom_call")
    (_, out), grads = grad_flash(q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        (_, out_ref), grads_ref = jax.jit(jax.value_and_grad(
            loss_ref, argnums=(0, 1, 2), has_aux=True))(*f32)
    errs = {}
    for name, got, want in (("out", out, out_ref),
                            ("dq", grads[0], grads_ref[0]),
                            ("dk", grads[1], grads_ref[1]),
                            ("dv", grads[2], grads_ref[2])):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == (B, S, H, D) and np.isfinite(got).all(),
              f"S={S} {name}: finite, shape {(B, S, H, D)}")
        err = float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))
        check(err <= KERNEL_TOL,
              f"S={S} causal={causal} padded={padded} {name}: max error "
              f"{err:.2e} of the largest value <= {KERNEL_TOL}")
        errs[name] = round(err, 5)
    return errs


def kernel_phase() -> dict:
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    require_tpu()
    return {
        "causal_s2048": kernel_case(2048, True, False),
        "causal_s4096": kernel_case(4096, True, False),
        "padded_noncausal_s1000": kernel_case(1000, False, True),
    }


# ----------------------------------------------------------------- train

def run_steps(step, carry, n_steps: int):
    """`n_steps` calls of carry, loss = step(carry), dispatched one by
    one from the host, each ending in block_until_ready."""
    import jax

    losses, seconds = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        carry, loss = step(carry)
        jax.block_until_ready((carry, loss))
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return carry, losses, seconds


def check_losses(tag: str, losses):
    import math

    check(all(math.isfinite(x) for x in losses),
          f"({tag}) every loss is finite: "
          + " ".join(f"{x:.4f}" for x in losses))
    check(losses[-1] < losses[0],
          f"({tag}) loss fell over {len(losses)} steps on the fixed batch: "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}")


def median_ms(seconds) -> float:
    import statistics

    return round(statistics.median(seconds) * 1e3, 2)


def train_phase(seq: int = SEQ, batch_per_chip: int = BATCH_PER_CHIP,
                steps: int = STEPS, model_kw=None, on_tpu: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.train import lm_loss, make_train_step
    from horovod_tpu.utils.compat import set_mesh
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = require_tpu() if on_tpu else jax.devices()
    n = len(devices)
    result = {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n},
        "versions": versions(), "cache_dir": cache_dir,
        "compile_seconds": {}, "step_ms": {}, "losses": {},
    }

    hvd.init()
    spec = get_model(MODEL)
    model = spec.make_model(attn_impl="flash", max_len=seq,
                            logits_dtype=jnp.bfloat16, **(model_kw or {}))
    ids = spec.make_batch(batch_per_chip * n, seq_len=seq)[0]

    def lowered_has_kernel(text: str, tag: str):
        if on_tpu:
            check("tpu_custom_call" in text
                  and "flash_attention_fwd" in text
                  and "flash_attention_bwd" in text,
                  f"({tag}) the lowered step holds the Mosaic custom calls "
                  "of the flash forward and backward kernels")

    # (a) make_train_step over create_mesh({"dp": n}) -------------------
    print(f"train (a): make_train_step, dp={n}, global batch "
          f"{batch_per_chip * n} x {seq}", flush=True)
    mesh = create_mesh({"dp": n})
    build = make_train_step(model, optax.adamw(LEARNING_RATE), lm_loss,
                            mesh=mesh)
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), ids)
    state = init_fn(jax.random.PRNGKey(0))
    params0 = jax.device_get(state.params)  # the same start for (b)
    ids_a = jax.device_put(ids, step_fn.shardings[1])
    with set_mesh(mesh):
        lowered = step_fn.__wrapped__.lower(state, ids_a)
    lowered_has_kernel(lowered.as_text(), "a")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    result["compile_seconds"]["make_train_step"] = round(
        time.perf_counter() - t0, 2)
    if n > 1:
        check(all(len(leaf.sharding.device_set) == n
                  for leaf in jax.tree.leaves(state.params)),
              f"every parameter leaf's sharding covers all {n} devices")
        shards = ids_a.addressable_shards
        check(len({s.device for s in shards}) == n
              and len({str(s.index) for s in shards}) == n,
              f"the batch has {n} distinct shards on {n} devices")
        check("all-reduce" in compiled.as_text(),
              "the compiled step holds an all-reduce")
    del compiled, lowered

    state, losses_a, secs_a = run_steps(
        lambda s: step_fn(s, ids_a), state, steps)
    check_losses("a", losses_a)
    if on_tpu:
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        check(all(b > 0 for b in in_use),
              f"bytes_in_use is non-zero on every device after a step: "
              f"{[round(b / 2**30, 2) for b in in_use]} GiB")
        stats = devices[0].memory_stats()
        result["memory_gib"] = {
            k: round(stats[k] / 2**30, 2)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                      "peak_bytes_reserved", "bytes_limit")}
    # The first step pays the executable's load, and jit's own compile
    # of the program the AOT path above just compiled — a persistent
    # cache hit.
    result["compile_seconds"]["make_train_step_first_call"] = round(
        secs_a[0], 2)
    result["step_ms"]["make_train_step"] = median_ms(secs_a[1:])
    result["losses"]["make_train_step"] = losses_a
    del state

    if n > 1:
        # The whole global batch does not fit one 16 GB chip at this
        # shape, so the one-device mesh takes it one shard at a time,
        # each from the same initial state; the mean of equal-sized
        # shards' mean losses is the global mean loss.
        print("train (a'): the same global batch on a one-device mesh",
              flush=True)
        mesh1 = create_mesh({"dp": 1}, devices=devices[:1])
        build1 = make_train_step(model, optax.adamw(LEARNING_RATE), lm_loss,
                                 mesh=mesh1, donate=False)
        init1, step1, _ = build1(jax.random.PRNGKey(0), ids[:batch_per_chip])
        state1 = init1(jax.random.PRNGKey(0))
        one = [float(step1(state1, ids[i:i + batch_per_chip])[1])
               for i in range(0, len(ids), batch_per_chip)]
        loss1 = float(np.mean(one))
        check(abs(losses_a[0] - loss1) <= LOSS_TOL * abs(loss1),
              f"step-0 loss at dp={n} ({losses_a[0]:.5f}) equals the "
              f"one-device mesh's ({loss1:.5f}) to {LOSS_TOL:g} relative")
        del state1, init1, step1

    # (b) the README's lines: DistributedOptimizer inside wrap_step ----
    print(f"train (b): hvd.DistributedOptimizer in hvd.wrap_step over "
          f"hvd.mesh(), size {hvd.size()}", flush=True)
    check(hvd.size() == n, f"hvd.size() == {n}")
    tx = hvd.DistributedOptimizer(optax.adamw(LEARNING_RATE))

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, batch), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss))

    step_b = hvd.wrap_step(train_step, mesh=hvd.mesh(),
                           replicated_argnums=(0, 1),
                           donate_argnums=(0, 1))
    params = jax.device_put(params0, NamedSharding(hvd.mesh(), P()))
    del params0
    opt_state = tx.init(params)
    text_b = jax.jit(step_b).lower(params, opt_state, ids).as_text()
    lowered_has_kernel(text_b, "b")
    if n > 1:
        check("all_reduce" in text_b,
              "(b) the lowered step holds the gradient all_reduce")

    def call_b(carry):
        p, o, loss = step_b(*carry, ids)
        return (p, o), loss

    _, losses_b, secs_b = run_steps(call_b, (params, opt_state), steps)
    check_losses("b", losses_b)
    result["compile_seconds"]["wrap_step_first_call"] = round(secs_b[0], 2)
    result["step_ms"]["wrap_step"] = median_ms(secs_b[1:])
    result["losses"]["wrap_step"] = losses_b
    check(abs(losses_a[0] - losses_b[0]) <= LOSS_TOL * abs(losses_a[0]),
          f"step 0 of (a) {losses_a[0]:.5f} and (b) {losses_b[0]:.5f} "
          f"agree to {LOSS_TOL:g} relative from the same parameters")
    if n > 1:
        result["flash_under_dp"] = (
            "partial-manual jax.shard_map(axis_names={'dp'}) "
            "(utils/compat.py has one branch)")
    hvd.shutdown()
    return result


# -------------------------------------------------------------- launcher

def held_chips() -> list:
    """Chip device nodes this process has open, from the OS."""
    found = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        base = target.rsplit("/", 1)[-1]
        if (target.startswith("/dev/vfio/") and base.isdigit()) \
                or target.startswith("/dev/accel"):
            found.add(target)
    return sorted(found)


def worker(on_tpu: bool = True) -> None:
    """One launched rank (the shape of examples/jax_mnist.py): gradients
    under jit on this rank's own chip, an eager DistributedOptimizer
    update through the host engine."""
    signal.alarm(PHASE_SECONDS["launcher"])  # never outlives the phase
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.cc import native
    from horovod_tpu.models import MnistCNN

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    check(hvd.mode() == "process", f"[{rank}] process mode under the launcher")
    check(native.status()["loaded"],
          f"[{rank}] the engine's native core is loaded (built from "
          "cc/core.cc, not the NumPy fallback)")
    if on_tpu:
        local = jax.local_devices()
        check(len(local) == 1 and local[0].platform == "tpu",
              f"[{rank}] jax.local_devices() is exactly one TPU device: "
              f"{local}")
        jnp.zeros(()).block_until_ready()  # the chip is open from here
        # Pinned processes each number their only device 0, so the jax
        # id cannot tell chips apart; the device node the process holds
        # open and the chip the launcher assigned can.
        mine = (held_chips(), os.environ.get("TPU_VISIBLE_CHIPS"))
        everyone = hvd.allgather_object(mine)
        if size > 1:
            check(all(len(chips) == 1 for chips, _ in everyone)
                  and len({chips[0] for chips, _ in everyone}) == size,
                  f"[{rank}] {size} workers hold {size} distinct chips: "
                  f"{everyone}")
            check(sorted(v for _, v in everyone)
                  == [str(i) for i in range(size)],
                  f"[{rank}] the launcher gave local rank i chip i")

    # One allreduce against its closed form.
    got = hvd.allreduce(np.full(1000, rank + 1.0, np.float32), op=hvd.Sum)
    check(np.allclose(np.asarray(got), size * (size + 1) / 2.0),
          f"[{rank}] allreduce(rank+1, Sum) == {size * (size + 1) / 2.0}")

    rng = np.random.RandomState(rank)
    x = rng.rand(64, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.int32)
    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(rank), x)
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    @jax.jit
    def grad_step(params, bx, by):
        def loss_fn(p):
            logp = jax.nn.log_softmax(model.apply(p, bx))
            return -jnp.mean(jnp.sum(jax.nn.one_hot(by, 10) * logp, axis=-1))

        return jax.value_and_grad(loss_fn)(params)

    losses = []
    for _ in range(5):
        loss, grads = grad_step(params, x, y)
        if on_tpu:
            check(all(leaf.devices() == {jax.local_devices()[0]}
                      for leaf in jax.tree.leaves(grads)),
                  f"[{rank}] gradients were computed on this rank's chip")
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"[{rank}] loss finite and falling: {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    # Ranks saw different data; equal parameters afterwards mean every
    # update used the engine's averaged gradients.
    digest = float(sum(np.abs(np.asarray(leaf, np.float64)).sum()
                       for leaf in jax.tree.leaves(params)))
    digests = hvd.allgather_object(digest)
    check(all(abs(d - digests[0]) <= 1e-6 * abs(digests[0])
              for d in digests),
          f"[{rank}] parameters agree across ranks after the updates")
    hvd.shutdown()


def launcher_phase(n_chips: int) -> dict:
    """Never touches jax: its workers need the chips."""
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # exported: the launched workers share it
    cc = os.path.join(REPO, "horovod_tpu", "cc")
    subprocess.run(["make", "-C", cc, "clean"], check=True)
    subprocess.run(["make", "-C", cc, "-s"], check=True)
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(n_chips), sys.executable, os.path.abspath(__file__),
           "--phase", "worker"]
    print("launcher:", " ".join(cmd), flush=True)
    t0 = time.perf_counter()
    rc = subprocess.run(cmd, cwd=REPO,
                        timeout=PHASE_SECONDS["launcher"] - 30).returncode
    check(rc == 0, f"hvdrun -np {n_chips} exited 0 (got {rc})")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "the launcher phase's parent initialised no jax backend")
    return {"np": n_chips, "seconds": round(time.perf_counter() - t0, 1)}


# ---------------------------------------------------------------- parent

def run_phase(name: str, extra: list, seconds: float) -> dict:
    """One phase in a process of its own; its result, or SmokeFailure.
    Its output goes to stderr so that stdout ends in the result line."""
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "result.json")
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
               "--result", out] + extra
        print(f"=== phase {name} (cap {seconds:.0f}s)", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=sys.stderr,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=seconds)
        except subprocess.TimeoutExpired:
            rc = "timeout"
        finally:
            try:  # the phase and anything left in its process group
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
        took = time.perf_counter() - t0
        if rc != 0 or not os.path.exists(out):
            raise SmokeFailure(f"phase {name} failed (exit {rc}, "
                               f"{took:.0f}s)")
        with open(out) as f:
            result = json.load(f)
    result["phase_seconds"] = round(took, 1)
    return result


def parent() -> int:
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not platforms:
        # Ask for the TPU by name, so that a chip that cannot be opened
        # is an error and not a CPU run.
        os.environ["JAX_PLATFORMS"] = "tpu"
    elif platforms.split(",")[0].strip() != "tpu":
        print(f"chip_smoke.py: JAX_PLATFORMS={platforms!r} names a platform "
              "other than the TPU; this program runs on the chip only and "
              "will not override the variable", file=sys.stderr)
        return 2
    deadline = time.monotonic() + TOTAL_SECONDS
    phases = {}
    try:
        for name in ("kernel", "train", "launcher"):
            left = deadline - time.monotonic()
            if left < 30:
                raise SmokeFailure(f"no time left for phase {name}")
            extra = []
            if name == "launcher":
                extra = ["--chips", str(phases["train"]["device"]["count"])]
            phases[name] = run_phase(name, extra,
                                     min(PHASE_SECONDS[name], left))
    except SmokeFailure as exc:
        print(f"chip_smoke.py: FAILED: {exc}", file=sys.stderr)
        return 1
    train = phases["train"]
    # Information for the reader, not a claim: the line before the last.
    print("report: " + json.dumps({
        "versions": train["versions"],
        "cache_dir": train["cache_dir"],
        "compile_seconds": train["compile_seconds"],
        "step_ms": train["step_ms"],
        "memory_gib": train["memory_gib"],
        "model": {"name": MODEL, "seq": SEQ,
                  "batch_per_chip": BATCH_PER_CHIP, "steps": STEPS},
        "phase_seconds": {k: v["phase_seconds"] for k, v in phases.items()},
        "kernel_max_error": {k: v for k, v in phases["kernel"].items()
                             if k != "phase_seconds"},
        "launcher": {"np": phases["launcher"]["np"]},
        "claim": None,
    }))
    # The result: exactly these keys, the device as jax reported it.
    print(json.dumps({"ok": True, "device": train["device"]}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", help="internal: run one phase in-process")
    ap.add_argument("--result", help="internal: where the phase's JSON goes")
    ap.add_argument("--chips", type=int, help="internal: launcher -np")
    args = ap.parse_args()
    if args.phase is None:
        return parent()
    try:
        if args.phase == "worker":
            worker()
            return 0
        # A phase never outlives its cap, whatever becomes of the parent.
        signal.alarm(PHASE_SECONDS[args.phase] + 30)
        if args.phase == "launcher":
            result = launcher_phase(args.chips)
        else:
            result = {"kernel": kernel_phase, "train": train_phase}[
                args.phase]()
    except SmokeFailure as exc:
        print(f"chip_smoke.py: phase {args.phase} FAILED: {exc}",
              file=sys.stderr)
        return 1
    with open(args.result, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
