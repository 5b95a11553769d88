"""Eager-engine allreduce micro-benchmark: latency / bandwidth vs size.

Measures the process-mode data plane the way the reference community
benchmarks Gloo vs MPI backends — per-op latency for small tensors and
achieved bus bandwidth for large ones, across the data-plane algorithms
(ref methodology: gloo ring allreduce,
horovod/common/ops/gloo_operations.cc:119-166).

Run under the launcher (2-8 processes):

    hvdrun -np 4 python examples/microbench_allreduce.py
    hvdrun -np 4 python examples/microbench_allreduce.py --algo ring
    hvdrun -np 2 python examples/microbench_allreduce.py --sizes 4194304

The default is a SWEEP: star vs single-shot ring vs segmented
(pipelined) ring — plus hierarchical ring when the launcher assigned a
multi-host topology — at 64KB / 1MB / 16MB. All the algorithm knobs
(HOROVOD_CPU_OPERATIONS, HOROVOD_RING_THRESHOLD,
HOROVOD_RING_SEGMENT_BYTES) are read per call, so one process flips
them between timed loops; every rank executes the same schedule, so
the flips stay collectively consistent. Rank 0 prints a table (GB/s)
and ONE JSON summary line.

`--mode transport` is the shared-memory acceptance measurement
(docs/running.md "Transports"): order-alternated paired rounds of the
16MB allreduce with the route flipped tcp<->shm between
barrier-separated timed loops (HOROVOD_TRANSPORT is read per call;
the overlays are established at init because this mode sets `auto`
before hvd.init()). Steady-state tensor names, so the response cache
engages and the loops measure the data plane, not negotiation.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import json
import time


def _set_algo_env(algo, segment_bytes):
    """Flip the per-call data-plane knobs. Identical on every rank —
    the launcher gave all workers the same argv — so the ring/star
    decision stays collectively consistent mid-run."""
    if algo == "auto":
        return  # measure exactly the as-launched library defaults
    os.environ.pop("HOROVOD_CPU_OPERATIONS", None)
    os.environ["HOROVOD_RING_SEGMENT_BYTES"] = "0"
    if algo == "star":
        os.environ["HOROVOD_CPU_OPERATIONS"] = "star"
    elif algo in ("ring", "hier"):
        os.environ["HOROVOD_RING_THRESHOLD"] = "0"
    elif algo == "segring":
        os.environ["HOROVOD_RING_THRESHOLD"] = "0"
        os.environ["HOROVOD_RING_SEGMENT_BYTES"] = str(segment_bytes)


def _bench_one(hvd, np, algo, count, iters, warmup):
    x = np.ones(count, np.float32)
    for i in range(warmup):
        hvd.allreduce(x, name=f"warm.{algo}.{count}.{i}")
    hvd.barrier()
    t0 = time.perf_counter()
    for i in range(iters):
        hvd.allreduce(x, name=f"bench.{algo}.{count}.{i}")
    dt = (time.perf_counter() - t0) / iters
    n = hvd.size()
    # Bus bandwidth uses the ring-allreduce wire factor 2(n-1)/n
    # (bytes each rank moves per link), the NCCL-tests convention.
    busbw = x.nbytes * 2 * (n - 1) / n / dt
    return {"algo": algo, "bytes": x.nbytes, "lat_us": dt * 1e6,
            "busbw_GBps": busbw / 1e9}


def _percentile(sorted_vals, q):
    return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]


def _bench_latency(hvd, np, basics, args):
    """Small-op enqueue-to-complete latency (p50/p99), swept over
    channel counts. Measures the engine path directly (enqueue +
    synchronize) so the number is the engine's latency, not the
    framework wrapper's. Compare a second launch with
    HOROVOD_CYCLE_EVENT_DRIVEN=0 to see the fixed-sleep floor this
    mode exists to demonstrate."""
    import os as _os
    import time as _time

    eng = basics.engine()
    x = np.ones(args.lat_count, np.float32)
    rows = []
    for nch in [1, args.channels]:
        _os.environ["HOROVOD_NUM_CHANNELS"] = str(nch)
        # Per-arm tensor name: a cached response replays the channel it
        # was negotiated with, so reusing one name across arms would
        # keep the second arm on the first arm's channel schedule.
        name = f"lat.c{nch}"
        for i in range(args.warmup):
            eng.synchronize(eng.enqueue_allreduce(x, name=name),
                            timeout=120)
        hvd.barrier()
        lats = []
        for i in range(args.iters):
            t0 = _time.perf_counter()
            eng.synchronize(eng.enqueue_allreduce(x, name=name),
                            timeout=120)
            lats.append(_time.perf_counter() - t0)
        hvd.barrier()
        lats.sort()
        rows.append({"channels": nch, "bytes": x.nbytes,
                     "p50_us": _percentile(lats, 0.5) * 1e6,
                     "p99_us": _percentile(lats, 0.99) * 1e6})
    return rows


def _bench_pipeline(hvd, np, basics, args):
    """Mixed-size pipelined workload: an async window of big allreduces
    with small allreduces interleaved (the gradient + metrics/sync-BN
    shape), channels=1 vs channels=N interleaved per round so the two
    arms see the same machine state. Fusion is disabled for the loop so
    each op is its own response and the channel schedule is what's
    measured."""
    import os as _os
    import time as _time

    eng = basics.engine()
    prev_fusion = eng.controller.fusion_threshold
    eng.controller.fusion_threshold = 1
    stream = []
    per_big = max(args.pipe_smalls // max(args.pipe_bigs, 1), 0)
    for _ in range(args.pipe_bigs):
        stream.append(args.pipe_big_count)
        stream.extend([args.pipe_small_count] * per_big)
    bufs = {n: np.ones(n, np.float32) for n in set(stream)}

    def one_round(nch, tag):
        _os.environ["HOROVOD_NUM_CHANNELS"] = str(nch)
        hvd.barrier()
        t0 = _time.perf_counter()
        handles = [
            eng.enqueue_allreduce(bufs[n], name=f"pipe.{tag}.{i}")
            for i, n in enumerate(stream)
        ]
        for h in handles:
            eng.synchronize(h, timeout=300)
        dt = _time.perf_counter() - t0
        hvd.barrier()
        return dt

    one_round(1, "w1")
    one_round(args.channels, "w2")
    pairs = [(one_round(1, f"a{r}"), one_round(args.channels, f"b{r}"))
             for r in range(args.pipe_rounds)]
    eng.controller.fusion_threshold = prev_fusion
    ratios = sorted(a / b for a, b in pairs)
    return {
        "stream_bytes": [n * 4 for n in stream],
        "channels": args.channels,
        "pairs_s": [[round(a, 4), round(b, 4)] for a, b in pairs],
        "ratios": [round(x, 3) for x in ratios],
        "median_speedup": round(_percentile(ratios, 0.5), 3),
    }


def _bench_transport(hvd, np, args, seg_bytes):
    """The shared-memory acceptance measurement: order-alternated
    paired rounds of the SAME segmented-ring schedule over tcp vs shm
    on co-located ranks (the paired-round protocol PR 3/4 used — on a
    shared box, sequential arms measure load drift, not transport
    cost). Requires launching with HOROVOD_TRANSPORT=shm/auto so the
    overlays exist (the mode sets auto itself before init); the route
    flips between barrier-separated timed loops, which is exactly the
    consistency contract the per-call knob documents."""
    import os as _os
    import time as _time

    _set_algo_env("segring", seg_bytes)
    x = np.ones(args.transport_count, np.float32)

    def timed(transport):
        # STEADY-STATE names (one per transport arm, reused every
        # iteration, like training reusing its gradient tensors): the
        # response cache engages after the warmup, so the timed loops
        # measure the data plane, not per-op negotiation — the same
        # protocol the PR 4 latency bench uses.
        _os.environ["HOROVOD_TRANSPORT"] = transport
        hvd.barrier()
        t0 = _time.perf_counter()
        for i in range(args.transport_iters):
            hvd.allreduce(x, name=f"tb.{transport}", op=hvd.Sum)
        dt = (_time.perf_counter() - t0) / args.transport_iters
        hvd.barrier()
        return dt

    timed("tcp")  # warmup: negotiate both arms' names once
    timed("shm")
    # Fail loudly if the shm arm silently fell back to tcp (no
    # co-located peers, or establishment failed): a ~1.0x "speedup"
    # from tcp-vs-tcp is worse than an error.
    shm_moved = hvd.metrics()["metrics"].get(
        'horovod_transport_bytes_total{direction="sent",transport="shm"}',
        0)
    assert shm_moved > 0, (
        "transport mode measured nothing on shm — are the ranks "
        "co-located and is the shm dir writable?")
    pairs = []
    for r in range(args.transport_rounds):
        if r % 2 == 0:
            a = timed("tcp")
            b = timed("shm")
        else:
            b = timed("shm")
            a = timed("tcp")
        pairs.append((a, b))
    _os.environ["HOROVOD_TRANSPORT"] = "auto"
    ratios = sorted(a / b for a, b in pairs)
    n = hvd.size()
    bus = x.nbytes * 2 * (n - 1) / n
    return {
        "bytes": int(x.nbytes),
        "iters": args.transport_iters,
        "pairs_ms": [[round(a * 1e3, 2), round(b * 1e3, 2)]
                     for a, b in pairs],
        "tcp_ms_median": round(_percentile(
            sorted(a for a, _ in pairs), 0.5) * 1e3, 2),
        "shm_ms_median": round(_percentile(
            sorted(b for _, b in pairs), 0.5) * 1e3, 2),
        "tcp_busbw_GBps": round(bus / _percentile(
            sorted(a for a, _ in pairs), 0.5) / 1e9, 3),
        "shm_busbw_GBps": round(bus / _percentile(
            sorted(b for _, b in pairs), 0.5) / 1e9, 3),
        "ratios": [round(r_, 3) for r_ in ratios],
        "median_speedup": round(_percentile(ratios, 0.5), 3),
    }


def _bench_compression(hvd, np, args):
    """Wire-compression acceptance measurement (docs/running.md "Wire
    compression"): order-alternated paired rounds of the SAME allreduce
    with the codec flipped none<->bf16 between barrier-separated timed
    loops, at 1MB and 16MB. Per-arm steady-state tensor names: the
    codec id is negotiated once per name and replays from the response
    cache (codec choice is cache-replay-stable), so each arm's loops
    measure the data plane under its own codec, not renegotiation.
    Wire bytes are measured from the transport byte counters — exact
    counter accounting, not computed from shapes."""
    import os as _os
    import time as _time

    # Every response in the sweep must be eligible regardless of size.
    _os.environ["HOROVOD_WIRE_COMPRESSION_MIN_BYTES"] = "0"

    def wire_sent(snap):
        return sum(v for k, v in snap.items()
                   if k.startswith("horovod_transport_bytes_total")
                   and 'direction="sent"' in k)

    def timed(mode, x, iters):
        _os.environ["HOROVOD_WIRE_COMPRESSION"] = mode
        hvd.barrier()
        before = wire_sent(hvd.metrics()["metrics"])
        t0 = _time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, name=f"cb.{mode}.{x.size}", op=hvd.Sum)
        dt = (_time.perf_counter() - t0) / iters
        hvd.barrier()
        sent = (wire_sent(hvd.metrics()["metrics"]) - before) / iters
        return dt, sent

    sizes = [262144, 4194304]  # 1MB / 16MB fp32
    out = []
    for count in sizes:
        x = np.ones(count, np.float32)
        timed("none", x, 2)  # warmup: negotiate both arms' names
        timed("bf16", x, 2)
        saved = hvd.metrics()["metrics"].get(
            'horovod_wire_bytes_saved_total{codec="bf16"}', 0)
        assert saved > 0, (
            "compression mode measured nothing on the bf16 arm — did "
            "the coordinator assign the codec?")
        pairs = []
        for r in range(args.compression_rounds):
            if r % 2 == 0:
                a = timed("none", x, args.compression_iters)
                b = timed("bf16", x, args.compression_iters)
            else:
                b = timed("bf16", x, args.compression_iters)
                a = timed("none", x, args.compression_iters)
            pairs.append((a, b))
        ratios = sorted(a[0] / b[0] for a, b in pairs)
        none_ms = _percentile(sorted(a[0] for a, _ in pairs), 0.5) * 1e3
        bf16_ms = _percentile(sorted(b[0] for _, b in pairs), 0.5) * 1e3
        none_wire = _percentile(sorted(a[1] for a, _ in pairs), 0.5)
        bf16_wire = _percentile(sorted(b[1] for _, b in pairs), 0.5)
        out.append({
            "bytes": int(x.nbytes),
            "pairs_ms": [[round(a[0] * 1e3, 2), round(b[0] * 1e3, 2)]
                         for a, b in pairs],
            "none_ms_median": round(none_ms, 2),
            "bf16_ms_median": round(bf16_ms, 2),
            "none_wire_bytes_per_op": int(none_wire),
            "bf16_wire_bytes_per_op": int(bf16_wire),
            "wire_reduction": round(none_wire / max(bf16_wire, 1), 3),
            "ratios": [round(v, 3) for v in ratios],
            "median_speedup": round(_percentile(ratios, 0.5), 3),
        })
    _os.environ["HOROVOD_WIRE_COMPRESSION"] = "none"
    return {"rows": out,
            "wire_bytes_saved": hvd.metrics()["metrics"].get(
                'horovod_wire_bytes_saved_total{codec="bf16"}', 0)}


def _bench_hier(hvd, np, args):
    """Host-arena acceptance measurement (docs/running.md
    "Transports"): order-alternated paired rounds of the SAME
    leader-mode hierarchical allreduce with the intra-host legs
    flipped per-pair-shm-rings <-> per-host-arena between
    barrier-separated timed loops (HOROVOD_HIER_ARENA is read per
    call; the arena capability bit was agreed at init). Launch over a
    (simulated) multi-host topology:

        HVDRUN_FORCE_LOCAL=1 hvdrun -np 4 -H hostA:2,hostB:2 \\
            python examples/microbench_allreduce.py --mode hier

    Two measurements per round, both order-alternated and paired:

    * ``data_plane`` — the schedule itself, driven directly on the
      backend under a channel scope (hvd.barrier()-synchronized starts,
      back-to-back ops). This is the leg comparison the arena exists
      for: both arms run the identical inter-host ring, only the
      intra-host legs differ.
    * ``engine`` — the same ops through the engine API (enqueue +
      synchronize, steady names so the response cache engages). On a
      box with cores >= ranks the two agree; on an oversubscribed box
      the engine's background negotiation steals CPU from the arena
      ROOT's critical path specifically (the root carries the whole
      fused reduce + inter ring + bcast), so the engine ratio reads
      lower — both are reported."""
    import os as _os
    import time as _time

    from horovod_tpu.backend.base import channel_scope
    from horovod_tpu.backend.ring import hierarchy_valid
    from horovod_tpu.common import basics

    eng = basics.engine()
    backend = eng.backend
    assert hierarchy_valid(backend), (
        "hier mode needs a multi-host topology (simulate one with "
        "-H hostA:2,hostB:2 and HVDRUN_FORCE_LOCAL=1)")
    _os.environ["HOROVOD_RING_THRESHOLD"] = "0"
    _os.environ["HOROVOD_HIERARCHICAL_MODE"] = "leader"
    x = np.ones(args.hier_count, np.float32)

    def timed_direct(arm):
        _os.environ["HOROVOD_HIER_ARENA"] = (
            "auto" if arm == "arena" else "off")
        hvd.barrier()
        t0 = _time.perf_counter()
        with channel_scope(0):
            for _ in range(args.hier_iters):
                backend._hierarchical_allreduce(x, hvd.Sum, owned=False)
        dt = (_time.perf_counter() - t0) / args.hier_iters
        hvd.barrier()
        return dt

    def timed_engine(arm):
        _os.environ["HOROVOD_HIER_ARENA"] = (
            "auto" if arm == "arena" else "off")
        hvd.barrier()
        t0 = _time.perf_counter()
        for i in range(args.hier_iters):
            eng.synchronize(
                eng.enqueue_allreduce(x, name=f"hb.{arm}"), timeout=300)
        dt = (_time.perf_counter() - t0) / args.hier_iters
        hvd.barrier()
        return dt

    for fn in (timed_direct, timed_engine):  # warmup both paths
        fn("rings")
        fn("arena")
    # Fail loudly if the arena arm silently fell back to the per-pair
    # rings (no host arena agreed): a ~1.0x "speedup" from
    # rings-vs-rings is worse than an error.
    arena_ops = hvd.metrics()["metrics"].get(
        "horovod_hier_arena_ops_total", 0)
    assert arena_ops > 0, (
        "hier mode measured nothing on the arena arm — are the hosts' "
        "slots co-located (distinct HOROVOD_HOSTNAME, shm writable)?")
    pairs = {"data_plane": [], "engine": []}
    for r in range(args.hier_rounds):
        for label, fn in (("data_plane", timed_direct),
                          ("engine", timed_engine)):
            if r % 2 == 0:
                a = fn("rings")
                b = fn("arena")
            else:
                b = fn("arena")
                a = fn("rings")
            pairs[label].append((a, b))

    def summarize(ps):
        ratios = sorted(a / b for a, b in ps)
        return {
            "pairs_ms": [[round(a * 1e3, 2), round(b * 1e3, 2)]
                         for a, b in ps],
            "rings_ms_median": round(_percentile(
                sorted(a for a, _ in ps), 0.5) * 1e3, 2),
            "arena_ms_median": round(_percentile(
                sorted(b for _, b in ps), 0.5) * 1e3, 2),
            "ratios": [round(v, 3) for v in ratios],
            "median_speedup": round(_percentile(ratios, 0.5), 3),
        }

    return {
        "bytes": int(x.nbytes),
        "iters": args.hier_iters,
        "data_plane": summarize(pairs["data_plane"]),
        "engine": summarize(pairs["engine"]),
        "median_speedup": summarize(pairs["data_plane"])["median_speedup"],
    }


# The traced-vs-eager benchmark pytree: ~2.36M params (>= the 1M-param
# acceptance shape), transformer-ish layer blocks with biases. ONE
# definition — scripts/perf_report.py imports it so its traced stages
# measure the same shape this microbench and docs/running.md describe.
GRAD_TREE_SHAPES = [(256, 1024), (1024,), (1024, 1024), (1024,),
                    (1024, 512), (512,), (512, 1024), (1024,)]


def _make_grad_tree(np, scale=1.0):
    rng = np.random.RandomState(0)
    return {f"layer{i}": (rng.randn(*s) * scale).astype(np.float32)
            for i, s in enumerate(GRAD_TREE_SHAPES)}


def build_traced_exchange(np, leaves):
    """The traced arm, shared by `--mode traced` and
    scripts/perf_report.py so both published numbers measure the SAME
    harness: a jitted shard_map grouped-psum AVERAGE over a local
    2-device mesh, per-device distinct grads via a stacked leading
    dim. Returns a zero-arg callable running one compiled exchange
    (compile + warmup happen here, outside any timed loop)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.utils.compat import shard_map

    assert len(jax.devices()) >= 2, (
        "the traced arm needs >= 2 local devices — force them with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=2 before "
        "jax's backend is created")
    mesh = create_mesh({"hvd": 2}, devices=jax.devices()[:2])
    stacked = [jnp.asarray(np.stack([v * (d + 1) for d in range(2)]))
               for v in leaves]

    def step(*xs):
        local = [jnp.squeeze(x, 0) for x in xs]
        return tuple(hvd.grouped_allreduce(local, op=hvd.Average))

    compiled = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=tuple(P("hvd") for _ in leaves),
        out_specs=tuple(P() for _ in leaves)))
    jax.block_until_ready(compiled(*stacked))  # compile outside timing
    return lambda: jax.block_until_ready(compiled(*stacked))


def _bench_traced(hvd, np, args):
    """Traced-vs-eager gradient-exchange acceptance measurement
    (docs/running.md "Traced collectives"): order-alternated paired
    rounds of the SAME pytree exchange, once through the eager engine
    (grouped allreduce, steady names, all ranks driving), once through
    the traced/XLA plane (a jitted shard_map grouped psum over rank 0's
    local 2-device mesh — single-controller, so only rank 0 drives it
    while the peers hold at the barrier). Both arms land in ONE JSON.

    Honest caveat (the PR 4/11 precedent): on this loopback container
    the traced arm's "wire" is an XLA all-reduce over two host buffers
    — it measures the DISPATCH cost floor, not the ICI win; and the two
    arms load the box differently (engine: both ranks + negotiation
    threads; traced: rank 0's XLA threads). The dispatch correctness
    (zero engine data-plane bytes — asserted in perf_smoke) is the
    acceptance gate, not this ratio."""
    assert hvd.size() == 2, (
        "traced mode is a PAIRED np=2 comparison (the traced arm is a "
        "2-device local mesh); launch with hvdrun -np 2 — at other "
        "sizes the two arms would do different amounts of work and the "
        "ratio would be meaningless")
    r = hvd.rank()
    tree = _make_grad_tree(np)
    leaves = list(tree.values())
    param_count = sum(int(v.size) for v in leaves)

    def eager_once(i):
        hvd.grouped_allreduce(leaves, name="tr.eager", op=hvd.Average)

    def timed_eager():
        hvd.barrier()
        t0 = time.perf_counter()
        for i in range(args.traced_iters):
            eager_once(i)
        dt = (time.perf_counter() - t0) / args.traced_iters
        hvd.barrier()
        return dt

    # Traced arm: rank 0's local 2-device mesh (devices forced in
    # main() before jax loaded), the shared harness — same world size
    # as the eager arm.
    run_traced = build_traced_exchange(np, leaves) if r == 0 else None

    def timed_traced():
        hvd.barrier()
        dt = 0.0
        if r == 0:
            t0 = time.perf_counter()
            for _ in range(args.traced_iters):
                run_traced()
            dt = (time.perf_counter() - t0) / args.traced_iters
        hvd.barrier()
        return dt

    timed_eager()  # warmup: negotiate the steady name
    timed_traced()
    pairs = []
    for rd in range(args.traced_rounds):
        if rd % 2 == 0:
            a = timed_eager()
            b = timed_traced()
        else:
            b = timed_traced()
            a = timed_eager()
        pairs.append((a, b))
    if r != 0:
        return None
    ratios = sorted(a / b for a, b in pairs)
    return {
        "param_count": param_count,
        "tensors": len(leaves),
        "bytes": int(sum(v.nbytes for v in leaves)),
        "iters": args.traced_iters,
        "pairs_ms": [[round(a * 1e3, 2), round(b * 1e3, 2)]
                     for a, b in pairs],
        "eager_ms_median": round(_percentile(
            sorted(a for a, _ in pairs), 0.5) * 1e3, 2),
        "traced_ms_median": round(_percentile(
            sorted(b for _, b in pairs), 0.5) * 1e3, 2),
        "ratios": [round(v, 3) for v in ratios],
        "median_speedup": round(_percentile(ratios, 0.5), 3),
    }


def _bench_reducescatter(hvd, np, args):
    """hvd.reducescatter timing (the ZeRO gradient leg): steady-state
    names so the engine's response cache engages, the same regime the
    `reducescatter_16mb_ms` perf_report stage gates."""
    count = args.rs_count
    n = hvd.size()
    x = np.ones(count, np.float32) * (hvd.rank() + 1)
    for i in range(args.warmup):
        hvd.reducescatter(x, op=hvd.Sum, name=f"warm.rs.{i}")
    hvd.barrier()
    t0 = time.perf_counter()
    for i in range(args.rs_iters):
        out = hvd.reducescatter(x, op=hvd.Sum, name=f"rs.{i}")
    dt = (time.perf_counter() - t0) / args.rs_iters
    assert out.shape[0] == count // n, out.shape
    # Reduce-scatter moves half an allreduce: (n-1)/n of the buffer
    # per link (the NCCL-tests convention).
    busbw = x.nbytes * (n - 1) / n / dt
    return {"bytes": x.nbytes, "iters": args.rs_iters,
            "lat_us": round(dt * 1e6, 1),
            "busbw_GBps": round(busbw / 1e9, 3)}


def _bench_zero(hvd, np, args):
    """ZeRO acceptance measurement (docs/running.md "ZeRO sharded
    optimizer state"): order-alternated paired rounds of the SAME
    gradient pytree through (a) a replicated update — grouped allreduce
    then a full-tree Adam update on every rank — and (b) the ZeRO path
    — grouped allreduce, owned-shard update, updated-segment allgather
    (`DistributedOptimizer(zero=1)`). Both arms ride the same engine
    grouped collectives with steady names; the delta is the update math
    plus the update allgather. The JSON carries MEASURED per-rank
    optimizer-state bytes for both arms — the (n-1)/n memory claim is
    reported from live buffers, not arithmetic."""
    import jax
    import optax

    n = hvd.size()
    tree = _make_grad_tree(np, scale=1e-2)
    keys = list(tree.keys())
    leaves = list(tree.values())
    params = {k: np.zeros_like(v) for k, v in tree.items()}
    inner = optax.adam(1e-3)

    tx_zero = hvd.DistributedOptimizer(inner, zero=1)
    s_zero = tx_zero.init(params)
    s_rep = inner.init(params)
    state_sharded = int(sum(v.nbytes for v in
                            jax.tree.leaves(s_zero.inner)))
    state_replicated = int(sum(
        np.asarray(v).nbytes for v in jax.tree.leaves(s_rep)))

    def rep_once():
        red = hvd.grouped_allreduce(leaves, name="zero.rep",
                                    op=hvd.Average)
        upd, s = inner.update(dict(zip(keys, red)), rep_box[0], params)
        rep_box[0] = s
        jax.block_until_ready(jax.tree.leaves(upd))

    def zero_once():
        upd, s = tx_zero.update(tree, zero_box[0], params)
        zero_box[0] = s
        jax.block_until_ready(jax.tree.leaves(upd))

    rep_box, zero_box = [s_rep], [s_zero]

    def timed(fn):
        hvd.barrier()
        t0 = time.perf_counter()
        for _ in range(args.zero_iters):
            fn()
        dt = (time.perf_counter() - t0) / args.zero_iters
        hvd.barrier()
        return dt

    timed(rep_once)  # warmup: negotiate the steady names
    timed(zero_once)
    pairs = []
    for rd in range(args.zero_rounds):
        if rd % 2 == 0:
            a = timed(rep_once)
            b = timed(zero_once)
        else:
            b = timed(zero_once)
            a = timed(rep_once)
        pairs.append((a, b))
    if hvd.rank() != 0:
        return None
    return {
        "param_count": int(sum(v.size for v in leaves)),
        "tensors": len(leaves),
        "bytes": int(sum(v.nbytes for v in leaves)),
        "iters": args.zero_iters,
        "state_bytes_replicated": state_replicated,
        "state_bytes_sharded": state_sharded,
        "state_saving": round(state_replicated / state_sharded, 3),
        "pairs_ms": [[round(a * 1e3, 2), round(b * 1e3, 2)]
                     for a, b in pairs],
        "replicated_ms_median": round(_percentile(
            sorted(a for a, _ in pairs), 0.5) * 1e3, 2),
        "zero_ms_median": round(_percentile(
            sorted(b for _, b in pairs), 0.5) * 1e3, 2),
        "zero_overhead": round(_percentile(
            sorted(b / a for a, b in pairs), 0.5), 3),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sizes", default="16384,262144,4194304",
                   help="comma-separated element counts (float32); the "
                        "default is 64KB / 1MB / 16MB")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--algo",
                   choices=["sweep", "auto", "ring", "segring", "star",
                            "hier"],
                   default="sweep",
                   help="one data-plane algorithm, or 'sweep' (default) "
                        "to compare them all in one run")
    p.add_argument("--segment-bytes", type=int, default=None,
                   help="HOROVOD_RING_SEGMENT_BYTES for the segmented "
                        "ring (default: the library default)")
    p.add_argument("--mode",
                   choices=["bw", "latency", "pipeline", "transport",
                            "compression", "hier", "traced", "zero",
                            "reducescatter"],
                   default="bw",
                   help="bw: the throughput sweep (default); latency: "
                        "small-op p50/p99 enqueue-to-complete, 1-vs-N "
                        "channels; pipeline: mixed-size async window, "
                        "channels=1 vs N paired rounds; transport: "
                        "tcp-vs-shm order-alternated paired rounds of "
                        "the segmented ring on co-located ranks; "
                        "compression: none-vs-bf16 order-alternated "
                        "paired rounds at 1MB/16MB with exact wire-byte "
                        "counter accounting; hier: leader-mode "
                        "hierarchical allreduce with the intra-host "
                        "legs flipped per-pair-rings vs per-host-arena "
                        "(needs a multi-host launch, e.g. simulated "
                        "-H hostA:2,hostB:2 with HVDRUN_FORCE_LOCAL=1); "
                        "traced: eager-engine vs traced-jit gradient "
                        "exchange on the same >=1M-param pytree, "
                        "order-alternated paired rounds (launch with "
                        "hvdrun -np 2); zero: replicated-update vs "
                        "ZeRO reduce/update/allgather on the same "
                        "pytree with measured per-rank state bytes "
                        "(intended np=4); reducescatter: "
                        "hvd.reducescatter timing at --rs-count")
    p.add_argument("--channels", type=int, default=2,
                   help="the N in the 1-vs-N channel comparisons")
    p.add_argument("--lat-count", type=int, default=16384,
                   help="latency-mode element count (default 64KB)")
    p.add_argument("--pipe-rounds", type=int, default=5)
    p.add_argument("--pipe-bigs", type=int, default=2)
    p.add_argument("--pipe-smalls", type=int, default=48)
    p.add_argument("--pipe-big-count", type=int, default=2097152,
                   help="big-op element count (default 8MB)")
    p.add_argument("--pipe-small-count", type=int, default=16384,
                   help="small-op element count (default 64KB)")
    p.add_argument("--transport-count", type=int, default=4194304,
                   help="transport-mode element count (default 16MB)")
    p.add_argument("--transport-iters", type=int, default=5,
                   help="allreduces per timed arm in transport mode")
    p.add_argument("--transport-rounds", type=int, default=5,
                   help="tcp/shm paired rounds in transport mode")
    p.add_argument("--compression-iters", type=int, default=5,
                   help="allreduces per timed arm in compression mode")
    p.add_argument("--compression-rounds", type=int, default=5,
                   help="none/bf16 paired rounds in compression mode")
    p.add_argument("--hier-count", type=int, default=4194304,
                   help="hier-mode element count (default 16MB)")
    p.add_argument("--hier-iters", type=int, default=5,
                   help="allreduces per timed arm in hier mode")
    p.add_argument("--hier-rounds", type=int, default=5,
                   help="rings/arena paired rounds in hier mode")
    p.add_argument("--traced-iters", type=int, default=5,
                   help="exchanges per timed arm in traced mode")
    p.add_argument("--traced-rounds", type=int, default=5,
                   help="eager/traced paired rounds in traced mode")
    p.add_argument("--zero-iters", type=int, default=5,
                   help="updates per timed arm in zero mode")
    p.add_argument("--zero-rounds", type=int, default=5,
                   help="replicated/zero paired rounds in zero mode")
    p.add_argument("--rs-count", type=int, default=4194304,
                   help="reducescatter-mode element count (default "
                        "16MB)")
    p.add_argument("--rs-iters", type=int, default=10,
                   help="reducescatters per timed run")
    args = p.parse_args()

    if args.mode == "traced":
        # The traced arm needs a >= 2-device local mesh on rank 0; the
        # flag must be set before jax's backend is created (lazy, so
        # before the horovod_tpu import below touches jax). An existing
        # count is OVERRIDDEN — a stale =1 exported by an earlier run
        # would silently starve the mesh.
        import re

        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", os.environ.get("XLA_FLAGS", "")).strip()
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()

    if args.mode == "hier":
        # Overlay + arena establishment and the capability agreement
        # happen at init; the timed loops then flip only the intra-host
        # legs. Hard assignment like transport mode — an exported
        # HOROVOD_TRANSPORT=tcp would turn this into rings-vs-rings.
        os.environ["HOROVOD_TRANSPORT"] = "auto"
        os.environ.setdefault("HOROVOD_HIERARCHICAL_ALLREDUCE", "auto")

    if args.mode == "transport":
        # Overlay establishment happens at init; the timed loops then
        # flip only the per-call route. Hard assignment, not
        # setdefault: an exported HOROVOD_TRANSPORT=tcp would
        # otherwise silently turn the measurement into tcp-vs-tcp.
        os.environ["HOROVOD_TRANSPORT"] = "auto"

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.backend.ring import (
        DEFAULT_RING_SEGMENT_BYTES,
        hierarchy_valid,
    )
    from horovod_tpu.common import basics

    seg_bytes = (args.segment_bytes if args.segment_bytes is not None
                 else DEFAULT_RING_SEGMENT_BYTES)

    hvd.init()
    r, n = hvd.rank(), hvd.size()

    if args.mode == "latency":
        rows = _bench_latency(hvd, np, basics, args)
        if r == 0:
            print(f"{'channels':>8} {'bytes':>10} {'p50(us)':>12} "
                  f"{'p99(us)':>12}")
            for row in rows:
                print(f"{row['channels']:>8} {row['bytes']:>10} "
                      f"{row['p50_us']:>12.1f} {row['p99_us']:>12.1f}")
            print(json.dumps({
                "metric": "eager_allreduce_latency", "np": n,
                "event_driven": os.environ.get(
                    "HOROVOD_CYCLE_EVENT_DRIVEN", "1"),
                "rows": [{k: (round(v, 1) if isinstance(v, float) else v)
                          for k, v in row.items()} for row in rows]}))
        return

    if args.mode == "transport":
        summary = _bench_transport(hvd, np, args, seg_bytes)
        if r == 0:
            print(f"transport paired rounds (ms, tcp vs shm): "
                  f"{summary['pairs_ms']}")
            print(f"median speedup shm vs tcp: "
                  f"{summary['median_speedup']}x  "
                  f"(tcp {summary['tcp_busbw_GBps']} GB/s -> "
                  f"shm {summary['shm_busbw_GBps']} GB/s busbw)")
            print(json.dumps(dict(
                {"metric": "eager_allreduce_transport", "np": n},
                **summary)))
        return

    if args.mode == "compression":
        summary = _bench_compression(hvd, np, args)
        if r == 0:
            for row in summary["rows"]:
                print(f"compression {row['bytes']} B: none "
                      f"{row['none_ms_median']}ms vs bf16 "
                      f"{row['bf16_ms_median']}ms "
                      f"({row['median_speedup']}x), wire bytes "
                      f"{row['none_wire_bytes_per_op']} -> "
                      f"{row['bf16_wire_bytes_per_op']} "
                      f"({row['wire_reduction']}x fewer)")
            print(json.dumps(dict(
                {"metric": "eager_allreduce_compression", "np": n},
                **summary)))
        return

    if args.mode == "hier":
        summary = _bench_hier(hvd, np, args)
        if r == 0:
            for label in ("data_plane", "engine"):
                s = summary[label]
                print(f"hier {label} paired rounds (ms, rings vs "
                      f"arena): {s['pairs_ms']}")
                print(f"  median speedup arena legs vs per-pair rings "
                      f"({label}): {s['median_speedup']}x  "
                      f"(rings {s['rings_ms_median']}ms -> "
                      f"arena {s['arena_ms_median']}ms)")
            print(json.dumps(dict(
                {"metric": "eager_allreduce_hier", "np": n}, **summary)))
        return

    if args.mode == "traced":
        summary = _bench_traced(hvd, np, args)
        if r == 0:
            print(f"traced paired rounds (ms, eager-engine vs "
                  f"traced-jit): {summary['pairs_ms']}")
            print(f"median speedup traced vs eager: "
                  f"{summary['median_speedup']}x  "
                  f"(eager {summary['eager_ms_median']}ms -> "
                  f"traced {summary['traced_ms_median']}ms, "
                  f"{summary['param_count']} params / "
                  f"{summary['tensors']} tensors)")
            print(json.dumps(dict(
                {"metric": "allreduce_traced_vs_eager", "np": n},
                **summary)))
        return

    if args.mode == "zero":
        summary = _bench_zero(hvd, np, args)
        if r == 0:
            print(f"zero paired rounds (ms, replicated vs zero): "
                  f"{summary['pairs_ms']}")
            print(f"state bytes/rank: replicated "
                  f"{summary['state_bytes_replicated']} -> sharded "
                  f"{summary['state_bytes_sharded']} "
                  f"({summary['state_saving']}x saving at np={n}); "
                  f"step {summary['replicated_ms_median']}ms -> "
                  f"{summary['zero_ms_median']}ms "
                  f"({summary['zero_overhead']}x)")
            print(json.dumps(dict(
                {"metric": "zero_optimizer", "np": n}, **summary)))
        return

    if args.mode == "reducescatter":
        summary = _bench_reducescatter(hvd, np, args)
        if r == 0:
            print(f"reducescatter {summary['bytes']} B: "
                  f"{summary['lat_us']}us "
                  f"({summary['busbw_GBps']} GB/s busbw)")
            print(json.dumps(dict(
                {"metric": "eager_reducescatter", "np": n}, **summary)))
        return

    if args.mode == "pipeline":
        summary = _bench_pipeline(hvd, np, basics, args)
        if r == 0:
            print(f"pipeline rounds (s): {summary['pairs_s']}")
            print(f"median speedup channels={summary['channels']} vs 1: "
                  f"{summary['median_speedup']}x")
            print(json.dumps(dict(
                {"metric": "eager_allreduce_pipeline", "np": n},
                **summary)))
        return

    backend = basics.engine().backend if basics.engine() else None

    if args.algo in ("sweep",):
        algos = ["star", "ring", "segring"]
        hier_ok = backend is not None and hierarchy_valid(backend)
        if hier_ok:
            algos.append("hier")
    else:
        algos = [args.algo]
        hier_ok = backend is not None and hierarchy_valid(backend)

    rows, skipped = [], []
    for algo in algos:
        if algo == "hier":
            if not hier_ok:
                skipped.append({"algo": "hier",
                                "reason": "topology not hierarchical "
                                          "(needs local_size>1 and "
                                          "cross_size>1)"})
                continue
            # The hierarchical toggle is normally set at init from
            # HOROVOD_HIERARCHICAL_ALLREDUCE / autotune; for the sweep
            # every rank flips it at the same schedule point, which is
            # exactly the collective-consistency the gate needs.
            backend.hierarchical = True
        elif backend is not None and algo != "auto":
            # 'auto' measures the as-launched config untouched.
            backend.hierarchical = False
        _set_algo_env(algo, seg_bytes)
        for count in [int(s) for s in args.sizes.split(",")]:
            rows.append(_bench_one(hvd, np, algo, count,
                                   args.iters, args.warmup))

    if r == 0:
        print(f"{'algo':>8} {'bytes':>12} {'latency(us)':>14} "
              f"{'busbw(GB/s)':>12}")
        for row in rows:
            print(f"{row['algo']:>8} {row['bytes']:>12} "
                  f"{row['lat_us']:>14.1f} {row['busbw_GBps']:>12.3f}")
        for s in skipped:
            print(f"{s['algo']:>8} skipped: {s['reason']}")
        print(json.dumps({
            "metric": "eager_allreduce",
            "np": n,
            "algo": args.algo,
            "segment_bytes": seg_bytes,
            "rows": [{k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in row.items()} for row in rows],
            "skipped": skipped,
        }))


if __name__ == "__main__":
    main()
