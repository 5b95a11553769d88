#!/usr/bin/env python
"""Standardized perf report + CI regression gate (docs/health.md).

One harness that runs the repo's microbench stages — small-op latency,
ring / segmented-ring bandwidth, the tcp-vs-shm transport pair, the
two-level hierarchical allreduce, the 16MB reduce-scatter leg, the
np=4 ZeRO-1 optimizer step (plus its measured per-rank state bytes),
and a serving round-trip — and emits
one JSON report: medians over order-alternated rounds (the house
methodology from the PR 3/4/8 acceptance measurements: on a shared box,
sequential arms measure load drift, so stage order alternates per
round and the median of rounds is the stage value). The report stamps
``horovod_build_info`` (version + jax) so every number is attributable
to a build.

Every stage is a host timing over loopback TCP / shared memory on this
machine's CPU cores. They guard the CPU data plane (engine, transports,
serving front door) against regressions; none is a speed of the chip.
The chip's numbers come from ``benchmark/run.py`` and live in
``PERF_LEDGER.jsonl`` and ``PERF.md``.

Comparison: every stage is lower-is-better; a stage regresses when
``value / baseline > 1 + tolerance`` (strictly — the boundary passes).
Tolerances are per-stage (the baseline file may carry a
``tolerances`` map) with a generous default, because CI boxes are
noisy and a flaky gate is worse than none.

CI wiring (scripts/ci.sh): warn-by-default against the committed
``scripts/perf_baseline.json``; gating is the explicit opt-in (``--gate``).
The gate itself is proven live on every CI run: a clean back-to-back
run must pass, and a ``--replay --inject-slowdown 2.0`` of the same
measurements must trip it.

    python scripts/perf_report.py                         # measure, warn
    python scripts/perf_report.py --gate                  # measure, gate
    python scripts/perf_report.py --update-baseline       # refresh baseline
    python scripts/perf_report.py --replay r.json --baseline b.json \
        --inject-slowdown 2.0 --gate                      # gate self-test
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "scripts", "perf_baseline.json")
DEFAULT_TOLERANCE = 0.5

SCHEMA = 1


def _median(vals):
    s = sorted(vals)
    n = len(s)
    if n == 0:
        return float("nan")
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _quantile(sorted_vals, q):
    return sorted_vals[min(int(q * len(sorted_vals)),
                           len(sorted_vals) - 1)]


# ---------------------------------------------------------------------------
# Measurement workers (run under the process-mode launcher, like
# perf_smoke). Each returns {stage: seconds} for ONE round; main()
# aggregates rounds into medians.

def _engine_worker():
    """np=2 engine stages: latency / ring / segring / transport, in
    per-round alternating order."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    hvd.init()
    eng = basics.engine()
    rounds = int(os.environ["PERF_ROUNDS"])
    lat_iters = int(os.environ["PERF_LAT_ITERS"])
    bw_iters = int(os.environ["PERF_BW_ITERS"])
    tr_iters = int(os.environ["PERF_TR_ITERS"])
    lat_x = np.ones(16384, np.float32)     # 64KB
    bw_x = np.ones(262144, np.float32)     # 1MB
    tr_x = np.ones(1048576, np.float32)    # 4MB
    cmp_x = np.ones(4194304, np.float32)   # 16MB

    def set_algo(ring: bool, seg_bytes: int):
        os.environ.pop("HOROVOD_CPU_OPERATIONS", None)
        os.environ["HOROVOD_RING_THRESHOLD"] = "0" if ring else str(1 << 40)
        os.environ["HOROVOD_RING_SEGMENT_BYTES"] = str(seg_bytes)

    def stage_latency(tag):
        set_algo(False, 0)
        name = "pr.lat"
        for _ in range(3):
            eng.synchronize(eng.enqueue_allreduce(lat_x, name=name),
                            timeout=120)
        hvd.barrier()
        lats = []
        for _ in range(lat_iters):
            t0 = time.perf_counter()
            eng.synchronize(eng.enqueue_allreduce(lat_x, name=name),
                            timeout=120)
            lats.append(time.perf_counter() - t0)
        hvd.barrier()
        lats.sort()
        return _quantile(lats, 0.5)

    def _timed_allreduce(x, name, iters):
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            hvd.allreduce(x, name=name, op=hvd.Sum)
        dt = (time.perf_counter() - t0) / iters
        hvd.barrier()
        return dt

    def stage_ring(tag):
        set_algo(True, 0)
        return _timed_allreduce(bw_x, "pr.ring", bw_iters)

    def stage_segring(tag):
        set_algo(True, 1 << 18)
        return _timed_allreduce(bw_x, "pr.segring", bw_iters)

    def stage_transport(tag):
        """tcp-vs-shm paired inside the stage (order alternates with
        the round parity, the PR 8 protocol)."""
        set_algo(True, 1 << 18)

        def arm(transport):
            os.environ["HOROVOD_TRANSPORT"] = transport
            return _timed_allreduce(tr_x, f"pr.tr.{transport}", tr_iters)

        if tag % 2 == 0:
            tcp = arm("tcp")
            shm = arm("shm")
        else:
            shm = arm("shm")
            tcp = arm("tcp")
        os.environ["HOROVOD_TRANSPORT"] = "auto"
        return {"tcp": tcp, "shm": shm}

    def stage_compression(tag):
        """none-vs-bf16 paired inside the stage at 16MB (order
        alternates with the round parity, like the transport stage).
        Per-arm steady-state names: the codec id is negotiated once
        per name and replays from the response cache."""
        set_algo(True, 1 << 18)
        os.environ["HOROVOD_WIRE_COMPRESSION_MIN_BYTES"] = "0"

        def arm(mode):
            os.environ["HOROVOD_WIRE_COMPRESSION"] = mode
            return _timed_allreduce(cmp_x, f"pr.cmp.{mode}", tr_iters)

        if tag % 2 == 0:
            none = arm("none")
            bf16 = arm("bf16")
        else:
            bf16 = arm("bf16")
            none = arm("none")
        os.environ["HOROVOD_WIRE_COMPRESSION"] = "none"
        return {"none": none, "bf16": bf16}

    def stage_native(tag):
        """native-vs-fallback paired at 16MB over the segmented ring
        (order alternates with the round parity): the C++ kernel-port
        A/B (docs/native.md). HOROVOD_DISABLE_NATIVE is honored per
        call by cc/native.py, so flipping the env between arms flips
        the data plane live — no reload dance."""
        set_algo(True, 1 << 18)

        def arm(disabled):
            if disabled:
                os.environ["HOROVOD_DISABLE_NATIVE"] = "1"
            else:
                os.environ.pop("HOROVOD_DISABLE_NATIVE", None)
            name = "pr.nat.off" if disabled else "pr.nat.on"
            return _timed_allreduce(cmp_x, name, tr_iters)

        if tag % 2 == 0:
            on = arm(False)
            off = arm(True)
        else:
            off = arm(True)
            on = arm(False)
        os.environ.pop("HOROVOD_DISABLE_NATIVE", None)
        return {"on": on, "off": off}

    def stage_reducescatter(tag):
        """16MB reduce-scatter over the segmented ring: each rank
        leaves with its 1/n slice of the summed dim 0 — the ZeRO
        gradient leg (docs/running.md "ZeRO sharded optimizer state").
        The steady `pr.rs` name keeps the inner reduction on the
        response cache, so this tracks the cached-path cost
        head-to-head with the 16MB allreduce stages above."""
        set_algo(True, 1 << 18)
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(tr_iters):
            hvd.reducescatter(cmp_x, op=hvd.Sum, name="pr.rs")
        dt = (time.perf_counter() - t0) / tr_iters
        hvd.barrier()
        return dt

    stages = [
        ("latency_small_p50_s", stage_latency),
        ("ring_1mb_s", stage_ring),
        ("segring_1mb_s", stage_segring),
        ("transport_4mb_s", stage_transport),
        ("compression_16mb_s", stage_compression),
        ("native_ring_16mb_s", stage_native),
        ("reducescatter_16mb_s", stage_reducescatter),
    ]
    out = {name: [] for name, _ in stages}
    # Warmup round (negotiation, cache fill, shm establishment) —
    # discarded.
    for name, fn in stages:
        fn(0)
    for r in range(rounds):
        order = stages if r % 2 == 0 else list(reversed(stages))
        for name, fn in order:
            out[name].append(fn(r))
    rank = hvd.rank()
    hvd.shutdown()
    return {"rank": rank, "stages": out}


def _hier_worker():
    """np=4 simulated 2-host x 2-slot hierarchical allreduce: the 1MB
    auto-mode stage (tracking whatever the defaults resolve to) plus
    `hier_arena_16mb` — the tentpole shape, 16MB fp32 leader mode with
    the per-host arena intra-host legs pinned on."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rounds = int(os.environ["PERF_ROUNDS"])
    iters = int(os.environ["PERF_BW_ITERS"])
    x = np.ones(262144, np.float32)  # 1MB
    os.environ["HOROVOD_RING_THRESHOLD"] = "0"
    vals = []
    for _ in range(3):
        hvd.allreduce(x, name="pr.hier", op=hvd.Sum)
    for r in range(rounds):
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            hvd.allreduce(x, name="pr.hier", op=hvd.Sum)
        vals.append((time.perf_counter() - t0) / iters)
        hvd.barrier()

    os.environ["HOROVOD_HIERARCHICAL_MODE"] = "leader"
    os.environ["HOROVOD_HIER_ARENA"] = "auto"
    iters16 = int(os.environ["PERF_TR_ITERS"])
    x16 = np.ones(4194304, np.float32)  # 16MB
    vals16 = []
    for _ in range(2):
        hvd.allreduce(x16, name="pr.hier16", op=hvd.Sum)
    # Fail loudly if the arena legs silently fell back to the per-pair
    # rings (capability bit not agreed): a rings measurement must never
    # be archived under the hier_arena label.
    assert hvd.metrics()["metrics"].get(
        "horovod_hier_arena_ops_total", 0) > 0, (
        "hier_arena stage measured the ring fallback — is shm "
        "writable and are the simulated hosts' slots co-located?")
    for r in range(rounds):
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(iters16):
            hvd.allreduce(x16, name="pr.hier16", op=hvd.Sum)
        vals16.append((time.perf_counter() - t0) / iters16)
        hvd.barrier()
    rank = hvd.rank()
    hvd.shutdown()
    return {"rank": rank, "hier_1mb_s": vals,
            "hier_arena_16mb_s": vals16}


def _traced_worker():
    """np=2 traced-vs-eager gradient exchange (docs/running.md "Traced
    collectives"): order-alternated arms per round on the SAME ~2.4M
    param pytree — the eager engine's grouped allreduce (both ranks
    driving, steady names) vs the traced/XLA plane (a jitted shard_map
    grouped psum over rank 0's local 2-device mesh; peers hold at the
    barrier). Two stages land in the report: `traced_step_ms` (the
    tracked XLA-plane arm) and `traced_eager_step_ms` (the engine arm,
    riding along per the compression_none precedent so the report shows
    both planes' cost on THIS box)."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rounds = int(os.environ["PERF_ROUNDS"])
    iters = int(os.environ["PERF_TR_ITERS"])
    r = hvd.rank()

    # The canonical benchmark pytree AND the traced-arm harness —
    # imported, not copied, so this stage always measures exactly what
    # the microbench and docs/running.md document.
    from examples.microbench_allreduce import (
        _make_grad_tree,
        build_traced_exchange,
    )

    leaves = list(_make_grad_tree(np).values())

    def timed_eager():
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            hvd.grouped_allreduce(leaves, name="pr.tra.eager",
                                  op=hvd.Average)
        dt = (time.perf_counter() - t0) / iters
        hvd.barrier()
        return dt

    run_traced = build_traced_exchange(np, leaves) if r == 0 else None

    def timed_traced():
        hvd.barrier()
        dt = 0.0
        if r == 0:
            t0 = time.perf_counter()
            for _ in range(iters):
                run_traced()
            dt = (time.perf_counter() - t0) / iters
        hvd.barrier()
        return dt

    timed_eager()  # warmup: negotiate the steady names
    timed_traced()
    eager_vals, traced_vals = [], []
    for rd in range(rounds):
        if rd % 2 == 0:
            eager_vals.append(timed_eager())
            traced_vals.append(timed_traced())
        else:
            traced_vals.append(timed_traced())
            eager_vals.append(timed_eager())
    rank = hvd.rank()
    hvd.shutdown()
    return {"rank": rank, "traced_step_s": traced_vals,
            "traced_eager_step_s": eager_vals}


def _zero_worker():
    """np=4 ZeRO-1 optimizer step (docs/running.md "ZeRO sharded
    optimizer state"): the eager ``DistributedOptimizer(zero=1)`` path
    on the canonical ~2.4M-param microbench pytree — grouped gradient
    allreduce, owned-segment adam update, updated-segment allgather —
    with steady collective names (``zero.grads`` / ``zero.updates``)
    so the response cache engages. Besides the timing rounds it
    reports the MEASURED per-rank optimizer-state bytes (max across
    ranks; the element-block cut keeps ranks within one block of each
    other) and the replicated equivalent — the (n-1)/n memory number
    the mode exists for."""
    import numpy as np

    import jax
    import optax

    import horovod_tpu as hvd

    hvd.init()
    rounds = int(os.environ["PERF_ROUNDS"])
    iters = int(os.environ["PERF_TR_ITERS"])

    from examples.microbench_allreduce import _make_grad_tree

    grads = _make_grad_tree(np)
    params = {k: np.zeros_like(v) for k, v in grads.items()}
    inner = optax.adam(1e-3)
    tx = hvd.DistributedOptimizer(inner, zero=1)
    state_box = [tx.init(params)]
    sharded = int(sum(np.asarray(l).nbytes
                      for l in jax.tree.leaves(state_box[0].inner)))
    sharded = max(hvd.allgather_object(sharded))
    replicated = int(sum(
        int(np.prod(s.shape, dtype=np.int64)) * np.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(jax.eval_shape(inner.init, params))))

    def timed():
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            _, state_box[0] = tx.update(grads, state_box[0], params)
        dt = (time.perf_counter() - t0) / iters
        hvd.barrier()
        return dt

    timed()  # warmup: negotiate the steady names, fill the caches
    vals = [timed() for _ in range(rounds)]
    rank = hvd.rank()
    hvd.shutdown()
    return {"rank": rank, "zero_step_s": vals,
            "zero_state_bytes": sharded,
            "zero_state_replicated_bytes": replicated}


def _serving_worker():
    """np=2 serving round-trip: echo model over the SPMD round
    protocol, p50 of programmatic submit -> reply."""
    import horovod_tpu as hvd

    hvd.init()
    rounds = int(os.environ["PERF_ROUNDS"])
    n_req = int(os.environ["PERF_SERVE_REQS"])

    def model_fn(weights, payloads):
        return [p for p in payloads]

    rank = hvd.rank()
    if rank != 0:
        hvd.serving.serve(model_fn, weights={})
        hvd.shutdown()
        return {"rank": rank}

    import threading

    from horovod_tpu.serving import InferenceFrontend

    frontend = InferenceFrontend(port=None)
    vals = []

    def drive():
        for _ in range(rounds):
            lats = []
            for _ in range(n_req):
                t0 = time.perf_counter()
                req = frontend.submit(1.0)
                assert req is not None
                assert req.wait(timeout=60)
                lats.append(time.perf_counter() - t0)
            lats.sort()
            vals.append(_quantile(lats, 0.5))
        frontend.request_stop()

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    report = hvd.serving.serve(model_fn, weights={}, frontend=frontend,
                               tick_seconds=0.05)
    t.join(timeout=60)
    hvd.shutdown()
    return {"rank": 0, "serving_rtt_p50_s": vals, "rounds": report}


# ---------------------------------------------------------------------------
# Harness

def measure(rounds: int, quick: bool) -> dict:
    from horovod_tpu.common import telemetry
    from horovod_tpu.runner import run

    env = {
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "120",
        "PERF_ROUNDS": str(rounds),
        "PERF_LAT_ITERS": "10" if quick else "30",
        "PERF_BW_ITERS": "3" if quick else "8",
        "PERF_TR_ITERS": "2" if quick else "4",
        "PERF_SERVE_REQS": "10" if quick else "30",
    }
    stages: dict = {}

    res = run(_engine_worker, np=2,
              extra_env=dict(env, HOROVOD_TRANSPORT="auto"))
    r0 = next(r for r in res if r["rank"] == 0)
    raw = r0["stages"]
    for name in ("latency_small_p50_s", "ring_1mb_s", "segring_1mb_s",
                 "reducescatter_16mb_s"):
        vals = raw[name]
        stages[name[:-2] + "_ms"] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }
    tr = raw["transport_4mb_s"]
    for arm in ("tcp", "shm"):
        vals = [d[arm] for d in tr]
        stages[f"transport_{arm}_4mb_ms"] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }
    # Wire compression (docs/running.md "Wire compression"):
    # `compression_16mb_ms` is the tracked bf16 arm; the none arm rides
    # along so the report shows the codec's cost/benefit on THIS box
    # (loopback has no wire to save — real NICs are where bf16 wins).
    cmp = raw["compression_16mb_s"]
    for arm, name in (("bf16", "compression_16mb_ms"),
                      ("none", "compression_none_16mb_ms")):
        vals = [d[arm] for d in cmp]
        stages[name] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }
    # Native kernel A/B (docs/native.md): `native_ring_16mb_ms` is the
    # tracked arm (kernels on — what production runs); the numpy
    # fallback arm rides along so every report shows the port's win on
    # THIS box.
    nat = raw["native_ring_16mb_s"]
    for arm, name in (("on", "native_ring_16mb_ms"),
                      ("off", "native_off_ring_16mb_ms")):
        vals = [d[arm] for d in nat]
        stages[name] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }

    os.environ["HVDRUN_FORCE_LOCAL"] = "1"
    res = run(_hier_worker, np=4, hosts="hostA:2,hostB:2",
              extra_env=dict(env, HVDRUN_FORCE_LOCAL="1",
                             HOROVOD_TRANSPORT="auto",
                             HOROVOD_HIERARCHICAL_ALLREDUCE="auto"))
    hier0 = next(r for r in res if r.get("rank") == 0)
    for key, name in (("hier_1mb_s", "hier_1mb_ms"),
                      ("hier_arena_16mb_s", "hier_arena_16mb_ms")):
        vals = hier0[key]
        stages[name] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }

    res = run(_traced_worker, np=2,
              extra_env=dict(
                  env,
                  XLA_FLAGS="--xla_force_host_platform_device_count=2",
                  HOROVOD_TRANSPORT="auto"))
    tr0 = next(r for r in res if r.get("rank") == 0)
    for key, name in (("traced_step_s", "traced_step_ms"),
                      ("traced_eager_step_s", "traced_eager_step_ms")):
        vals = tr0[key]
        stages[name] = {
            "unit": "ms",
            "rounds": [round(v * 1e3, 4) for v in vals],
            "value": round(_median(vals) * 1e3, 4),
        }

    res = run(_zero_worker, np=4,
              extra_env=dict(env, HOROVOD_TRANSPORT="auto"))
    z0 = next(r for r in res if r.get("rank") == 0)
    vals = z0["zero_step_s"]
    stages["zero_step_ms"] = {
        "unit": "ms",
        "rounds": [round(v * 1e3, 4) for v in vals],
        "value": round(_median(vals) * 1e3, 4),
    }
    # State bytes are a memory measurement, not a timing: exact
    # integers, one round. Lower-is-better still holds — an
    # ownership-cut regression that grows a rank's shard trips the
    # gate like any slowdown.
    stages["zero_state_bytes"] = {
        "unit": "bytes",
        "rounds": [z0["zero_state_bytes"]],
        "value": z0["zero_state_bytes"],
        "replicated_bytes": z0["zero_state_replicated_bytes"],
    }

    res = run(_serving_worker, np=2, extra_env=env)
    vals = next(r for r in res if r.get("rank") == 0)["serving_rtt_p50_s"]
    stages["serving_rtt_p50_ms"] = {
        "unit": "ms",
        "rounds": [round(v * 1e3, 4) for v in vals],
        "value": round(_median(vals) * 1e3, 4),
    }

    return {
        "schema": SCHEMA,
        "kind": "horovod_perf_report",
        "time": time.time(),
        "build": telemetry.build_info(),
        "rounds": rounds,
        "quick": quick,
        "stages": stages,
    }


# ---------------------------------------------------------------------------
# Baseline comparison (pure — unit-tested on synthetic reports)

def compare(report: dict, baseline: dict,
            default_tolerance: float = DEFAULT_TOLERANCE) -> list:
    """Per-stage verdicts of `report` against `baseline`. Every stage
    is lower-is-better; regression iff ratio > 1 + tolerance
    (STRICTLY — the boundary passes). A stage the baseline names but
    the report lacks is `missing` (fails the gate: a silently dropped
    measurement must not read as a pass); NaN measurements are
    `invalid`; an unusable baseline entry is `skipped` (a broken
    baseline must not fail every future run); stages only the report
    has are `new` (informational)."""
    tolerances = baseline.get("tolerances", {})
    verdicts = []
    rep_stages = report.get("stages", {})
    base_stages = baseline.get("stages", {})
    for name in sorted(base_stages):
        tol = float(tolerances.get(name, default_tolerance))
        base_val = base_stages[name].get("value")
        ent = {"stage": name, "baseline": base_val, "tolerance": tol}
        if (not isinstance(base_val, (int, float)) or base_val <= 0
                or (isinstance(base_val, float) and math.isnan(base_val))):
            ent.update(status="skipped", value=None, ratio=None)
            verdicts.append(ent)
            continue
        rep = rep_stages.get(name)
        val = rep.get("value") if isinstance(rep, dict) else None
        if rep is None:
            ent.update(status="missing", value=None, ratio=None)
            verdicts.append(ent)
            continue
        if (not isinstance(val, (int, float))
                or (isinstance(val, float) and math.isnan(val))):
            ent.update(status="invalid", value=val, ratio=None)
            verdicts.append(ent)
            continue
        ratio = val / base_val
        ent.update(
            status="regression" if ratio > 1.0 + tol else "ok",
            value=val, ratio=round(ratio, 4))
        verdicts.append(ent)
    for name in sorted(set(rep_stages) - set(base_stages)):
        rep = rep_stages[name]
        verdicts.append({
            "stage": name, "status": "new",
            "value": rep.get("value") if isinstance(rep, dict) else None,
            "baseline": None, "ratio": None, "tolerance": None,
        })
    return verdicts


GATE_FAIL_STATES = ("regression", "missing", "invalid")


def gate_verdict(verdicts: list) -> bool:
    """True = pass. missing/invalid fail alongside regressions: a
    gate that can be passed by not measuring is not a gate."""
    return not any(v["status"] in GATE_FAIL_STATES for v in verdicts)


def render(verdicts: list) -> str:
    lines = [f"{'stage':<26} {'value':>12} {'baseline':>12} "
             f"{'ratio':>7} {'tol':>5}  status"]
    for v in verdicts:
        val = f"{v['value']:.3f}" if isinstance(
            v["value"], (int, float)) else "-"
        base = f"{v['baseline']:.3f}" if isinstance(
            v["baseline"], (int, float)) else "-"
        ratio = f"{v['ratio']:.3f}" if v["ratio"] is not None else "-"
        tol = f"{v['tolerance']:.2f}" if v["tolerance"] is not None else "-"
        lines.append(f"{v['stage']:<26} {val:>12} {base:>12} "
                     f"{ratio:>7} {tol:>5}  {v['status']}")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", help="write the measured report JSON here")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline report to compare against "
                         "(default: scripts/perf_baseline.json)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 on regression/missing/invalid "
                         "(default: warn only)")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="default relative tolerance (baseline "
                         "`tolerances` map overrides per stage)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="order-alternated measurement rounds")
    ap.add_argument("--quick", action="store_true",
                    help="fewer iterations per stage (CI budget)")
    ap.add_argument("--replay",
                    help="skip measurement; load stage values from this "
                         "existing report (gate self-tests)")
    ap.add_argument("--inject-slowdown", type=float, default=0.0,
                    help="multiply every measured stage value by this "
                         "factor after measurement — proves the gate "
                         "trips (self-test)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the measured report to the baseline path")
    args = ap.parse_args()

    if args.replay:
        with open(args.replay) as f:
            report = json.load(f)
    else:
        report = measure(args.rounds, args.quick)

    if args.inject_slowdown > 0:
        report = json.loads(json.dumps(report))  # deep copy
        for st in report["stages"].values():
            if isinstance(st.get("value"), (int, float)):
                st["value"] = st["value"] * args.inject_slowdown
        report["injected_slowdown"] = args.inject_slowdown

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)

    if args.update_baseline:
        with open(args.baseline, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"baseline updated: {args.baseline}")
        return 0

    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; report only")
        print(json.dumps(report["stages"], indent=1, sort_keys=True))
        return 0

    with open(args.baseline) as f:
        baseline = json.load(f)
    verdicts = compare(report, baseline, args.tolerance)
    print(render(verdicts))
    print(json.dumps({
        "metric": "perf_report",
        "build": report.get("build"),
        "gate": args.gate,
        "pass": gate_verdict(verdicts),
        "stages": {v["stage"]: v["status"] for v in verdicts},
    }))
    if not gate_verdict(verdicts):
        bad = [v for v in verdicts if v["status"] in GATE_FAIL_STATES]
        msg = ", ".join(f"{v['stage']}={v['status']}" for v in bad)
        if args.gate:
            print(f"PERF GATE FAILED: {msg}", file=sys.stderr)
            return 1
        print(f"perf regression WARNING (not gating): {msg}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
