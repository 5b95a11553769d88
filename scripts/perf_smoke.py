#!/usr/bin/env python
"""Data-plane perf smoke: a real 2-worker loopback run over every ring
schedule, asserting completion and EXACT byte accounting — no flaky
throughput thresholds (CI boxes are too noisy for those; the numbers
live in examples/microbench_allreduce.py and scripts/perf_report.py
instead).

What it pins down:

* the zero-copy TCP data plane (sendmsg scatter-gather sends,
  recv_into receives, persistent peer senders) completes star,
  single-shot ring and segmented pipelined ring allreduces with
  correct results;
* `horovod_allreduce_bytes_total` accounts every enqueued payload byte
  exactly (iters x nbytes per rank) — the engine counts negotiated
  input bytes, so the number is deterministic regardless of which
  algorithm moved them;
* the new transport counters moved: `horovod_tcp_sendmsg_frames_total`
  > 0 on every rank and `horovod_ring_segments_total` > 0 wherever a
  ring schedule ran (and the segmented run produced strictly more
  segments than chunks);
* a 2-channel pipelined window (ring bigs on the bulk lane, star
  smalls on the latency lane, fusion off) still accounts every byte
  exactly and moves frames on channel tags 0, 1 and ctrl
  (`horovod_tcp_channel_frames_total`).

Run by scripts/ci.sh; also a manual repro tool:

    python scripts/perf_smoke.py        # the data-plane legs
    python scripts/perf_smoke.py zero   # np=4 ZeRO two-leg accounting
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ITERS = 4
COUNT = 1 << 16  # 256KB float32 — above the default ring threshold


def worker():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    expect_bytes = 0
    schedules = [
        ("star", {"HOROVOD_CPU_OPERATIONS": "star"}),
        ("ring", {"HOROVOD_RING_THRESHOLD": "0",
                  "HOROVOD_RING_SEGMENT_BYTES": "0"}),
        # 64KB segments over a 64KB chunk (np=2) -> >1 segment/chunk.
        ("segring", {"HOROVOD_RING_THRESHOLD": "0",
                     "HOROVOD_RING_SEGMENT_BYTES": str(1 << 16)}),
    ]
    seg_counts = {}
    for name, env in schedules:
        os.environ.pop("HOROVOD_CPU_OPERATIONS", None)
        os.environ.update(env)
        before = hvd.metrics()["metrics"].get(
            "horovod_ring_segments_total", 0)
        for i in range(ITERS):
            x = np.full(COUNT, float(hvd.rank() + 1), np.float32)
            out = np.asarray(hvd.allreduce(
                x, name=f"perf.{name}.{i}", op=hvd.Sum))
            assert out.shape == (COUNT,), out.shape
            assert float(out[0]) == sum(range(1, n + 1)), (name, out[0])
            expect_bytes += x.nbytes
        seg_counts[name] = (hvd.metrics()["metrics"].get(
            "horovod_ring_segments_total", 0) - before)

    # 2-channel pipelined run: an async window of ring bigs (bulk lane)
    # + star smalls (latency lane), fusion off so every op is its own
    # response. Byte accounting must stay EXACT with two channels in
    # flight, and the channel-tagged frame counters must show traffic on
    # both data lanes plus the control lane.
    from horovod_tpu.common import basics

    eng = basics.engine()
    prev_fusion = eng.controller.fusion_threshold
    eng.controller.fusion_threshold = 1
    os.environ.update({"HOROVOD_RING_THRESHOLD": "0",
                       "HOROVOD_RING_SEGMENT_BYTES": str(1 << 16),
                       "HOROVOD_NUM_CHANNELS": "2"})
    handles = []
    for i in range(ITERS):
        big = np.full(COUNT, float(hvd.rank() + 1), np.float32)
        small = np.full(1024, float(hvd.rank() + 1), np.float32)
        handles.append((eng.enqueue_allreduce(big, name=f"pc.big.{i}"),
                        COUNT, big.nbytes))
        handles.append((eng.enqueue_allreduce(small, name=f"pc.small.{i}"),
                        1024, small.nbytes))
        expect_bytes += big.nbytes + small.nbytes
    for h, count, _ in handles:
        out = np.asarray(eng.synchronize(h, timeout=120))
        assert out.shape == (count,), out.shape
        assert float(out[0]) == sum(range(1, n + 1)), out[0]
    hvd.barrier()
    eng.controller.fusion_threshold = prev_fusion

    snap = hvd.metrics()["metrics"]
    got = snap["horovod_allreduce_bytes_total"]
    assert got == expect_bytes, (
        f"allreduce_bytes_total accounting drifted: got {got}, "
        f"expected exactly {expect_bytes}")
    assert snap.get("horovod_tcp_sendmsg_frames_total", 0) > 0, snap
    # Channel-tag counters: bulk lane 0 (ring bigs), latency lane 1
    # (star smalls), and the control plane all moved frames.
    for label in ("0", "1", "ctrl"):
        key = f'horovod_tcp_channel_frames_total{{channel="{label}"}}'
        assert snap.get(key, 0) > 0, (label, sorted(
            k for k in snap if "channel_frames" in k))
    # Ring chunks: n per allreduce move as >=1 segment each on the send
    # side; the 64KB-segment run must split chunks further.
    assert seg_counts["star"] == 0, seg_counts
    assert seg_counts["ring"] >= ITERS, seg_counts
    assert seg_counts["segring"] > seg_counts["ring"], seg_counts
    checks = {"rank": hvd.rank(), "bytes": got, "segments": seg_counts}
    hvd.shutdown()
    return checks


def worker_shm():
    """Shared-memory transport smoke, launched with NO HOROVOD_TRANSPORT
    set — the `auto` DEFAULT must engage shm between these co-located
    ranks by itself (the ROADMAP-flagged default-flip assertion): star
    over shm p2p, ring over the per-pair shm rings, and the intra-host
    arena — engine byte accounting stays EXACT on every path, and the
    per-transport counters let main() assert exact conservation: every
    shm byte one rank sent, the other received."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    expect_bytes = 0
    schedules = [
        ("star", {"HOROVOD_CPU_OPERATIONS": "star"}),
        # CPU_OPERATIONS=ring pins the per-pair shm RINGS (the arena
        # would otherwise win the op registry).
        ("shmring", {"HOROVOD_CPU_OPERATIONS": "ring",
                     "HOROVOD_RING_THRESHOLD": "0",
                     "HOROVOD_RING_SEGMENT_BYTES": "0"}),
        ("arena", {"HOROVOD_RING_THRESHOLD": "0"}),
    ]
    for name, env in schedules:
        os.environ.pop("HOROVOD_CPU_OPERATIONS", None)
        os.environ.update(env)
        for i in range(ITERS):
            x = np.full(COUNT, float(hvd.rank() + 1), np.float32)
            out = np.asarray(hvd.allreduce(
                x, name=f"ps.{name}.{i}", op=hvd.Sum))
            assert out.shape == (COUNT,), out.shape
            assert float(out[0]) == sum(range(1, n + 1)), (name, out[0])
            expect_bytes += x.nbytes
    hvd.barrier()
    snap = hvd.metrics()["metrics"]
    got = snap["horovod_allreduce_bytes_total"]
    assert got == expect_bytes, (
        f"allreduce_bytes_total drifted on shm: got {got}, "
        f"expected exactly {expect_bytes}")
    shm_sent = snap.get(
        'horovod_transport_bytes_total{direction="sent",transport="shm"}',
        0)
    shm_recv = snap.get(
        'horovod_transport_bytes_total{direction="recv",transport="shm"}',
        0)
    assert shm_sent > 0 and shm_recv > 0, (
        "data plane never rode shared memory", sorted(
            k for k in snap if "transport_bytes" in k))
    checks = {"rank": hvd.rank(), "bytes": got,
              "shm_sent": shm_sent, "shm_recv": shm_recv}
    hvd.shutdown()
    return checks


def worker_compression():
    """Wire-compression smoke over the pinned tcp plane: compressed
    RING and STAR legs with EXACT accounting of BOTH sides of the
    ledger — `horovod_allreduce_bytes_total` keeps counting negotiated
    INPUT bytes (codec-independent by design: the engine records what
    the user enqueued), while `horovod_wire_bytes_saved_total{codec=}`
    must equal the closed-form per-frame savings:

    * ring (np=n, COUNT fp32 elems, bf16): each rank sends one
      COUNT/n-elem chunk per reduce-scatter step and one per allgather
      step (n-1 each), saving 2 bytes/elem -> per rank per op
      2*(n-1)*(COUNT/n)*2 bytes;
    * star: a worker's gather frame saves COUNT*2; the root saves
      (n-1)*COUNT*2 on its result broadcast (its own gather
      contribution never touches a wire and must NOT count).

    Compression counters fold into the per-transport accounting as
    true wire bytes: the same schedule's tcp sent bytes must SHRINK
    vs an uncompressed control leg (asserted), because the transport
    counters see the encoded frames — nothing is estimated."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    os.environ.update({"HOROVOD_WIRE_COMPRESSION_MIN_BYTES": "0",
                       "HOROVOD_RING_THRESHOLD": "0",
                       "HOROVOD_RING_SEGMENT_BYTES": "0"})

    def tcp_sent(snap):
        return snap.get(
            'horovod_transport_bytes_total'
            '{direction="sent",transport="tcp"}', 0)

    expect_bytes = 0
    expect_saved = 0
    per_elem = 2  # fp32 -> bf16
    tcp_deltas = {}
    legs = [
        ("none_ring", "none", {}),
        ("ring", "bf16", {}),
        ("star", "bf16", {"HOROVOD_CPU_OPERATIONS": "star"}),
    ]
    for name, mode, env in legs:
        os.environ.pop("HOROVOD_CPU_OPERATIONS", None)
        os.environ.update(env)
        os.environ["HOROVOD_WIRE_COMPRESSION"] = mode
        before = tcp_sent(hvd.metrics()["metrics"])
        for i in range(ITERS):
            # rank+1 is exactly representable in bf16, so the reduced
            # values — and the zero error-feedback residuals — stay
            # exact and the correctness assert needs no tolerance.
            x = np.full(COUNT, float(hvd.rank() + 1), np.float32)
            out = np.asarray(hvd.allreduce(
                x, name=f"pcmp.{name}.{i}", op=hvd.Sum))
            assert out.shape == (COUNT,), out.shape
            assert float(out[0]) == sum(range(1, n + 1)), (name, out[0])
            expect_bytes += x.nbytes
            if mode == "bf16":
                if name == "ring":
                    expect_saved += 2 * (n - 1) * (COUNT // n) * per_elem
                else:  # star
                    expect_saved += (n - 1) * COUNT * per_elem \
                        if hvd.rank() == 0 else COUNT * per_elem
        hvd.barrier()
        tcp_deltas[name] = tcp_sent(hvd.metrics()["metrics"]) - before
    os.environ["HOROVOD_WIRE_COMPRESSION"] = "none"

    snap = hvd.metrics()["metrics"]
    got = snap["horovod_allreduce_bytes_total"]
    assert got == expect_bytes, (
        f"allreduce_bytes_total drifted under compression: got {got}, "
        f"expected exactly {expect_bytes}")
    saved = snap.get('horovod_wire_bytes_saved_total{codec="bf16"}', 0)
    assert saved == expect_saved, (
        f"wire_bytes_saved accounting drifted: got {saved}, expected "
        f"exactly {expect_saved}")
    # True-wire-bytes fold: same ring schedule, compressed frames ->
    # fewer tcp bytes on the wire than the uncompressed control.
    assert tcp_deltas["ring"] < tcp_deltas["none_ring"], tcp_deltas
    checks = {"rank": hvd.rank(), "bytes": got, "saved": saved,
              "tcp_ring": tcp_deltas["ring"],
              "tcp_none": tcp_deltas["none_ring"]}
    hvd.shutdown()
    return checks


def worker_traced():
    """Traced-collectives smoke (docs/running.md "Traced collectives"):
    with a REAL process-mode engine alive, a jitted shard_map gradient
    exchange over the worker's local 2-device mesh must dispatch to the
    XLA plane and leave the engine data plane UNTOUCHED — XLA owns the
    wire, so `horovod_allreduce_bytes_total` and the transport byte
    counters must not move while `horovod_traced_ops_total` does. An
    eager control op first proves the engine counters DO move when the
    engine is used (a zero-delta assert against dead counters would
    pass vacuously)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.utils.compat import shard_map

    hvd.init()
    n = hvd.size()

    def engine_bytes(snap):
        return snap.get("horovod_allreduce_bytes_total", 0)

    def data_frames(snap):
        # Frames on NUMERIC (data) channels only: ctrl/health frames
        # keep flowing regardless (heartbeats, telemetry piggyback) and
        # must not fail the zero-data-plane assert.
        total = 0
        for k, v in snap.items():
            if k.startswith("horovod_tcp_channel_frames_total"):
                label = k.split('channel="')[1].split('"')[0]
                if label.isdigit():
                    total += v
        return total

    def traced_ops(snap):
        return sum(v for k, v in snap.items()
                   if k.startswith("horovod_traced_ops_total"))

    # Control: the eager plane moves engine bytes.
    x = np.full(COUNT, float(hvd.rank() + 1), np.float32)
    out = np.asarray(hvd.allreduce(x, name="ptr.ctrl", op=hvd.Sum))
    assert float(out[0]) == sum(range(1, n + 1)), out[0]
    snap = hvd.metrics()["metrics"]
    assert engine_bytes(snap) == x.nbytes, snap.get(
        "horovod_allreduce_bytes_total")

    # Traced leg: local 2-device mesh, jitted psum exchange. The
    # barrier settles the control op's in-flight frames before the
    # before-snapshot.
    assert len(jax.devices()) >= 2, "worker needs 2 forced CPU devices"
    mesh = create_mesh({"hvd": 2}, devices=jax.devices()[:2])
    hvd.barrier()
    snap = hvd.metrics()["metrics"]
    before_engine = engine_bytes(snap)
    before_frames = data_frames(snap)
    before_traced = traced_ops(snap)

    step = jax.jit(shard_map(
        lambda v: hvd.allreduce(v, op=hvd.Sum),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd")))
    g = jnp.arange(2 * COUNT, dtype=jnp.float32)
    for _ in range(ITERS):
        out_t = jax.block_until_ready(step(g))
    halves = np.asarray(g).reshape(2, -1)
    np.testing.assert_allclose(np.asarray(out_t),
                               np.tile(halves[0] + halves[1], 2))

    snap = hvd.metrics()["metrics"]
    traced_delta = traced_ops(snap) - before_traced
    engine_delta = engine_bytes(snap) - before_engine
    frames_delta = data_frames(snap) - before_frames
    assert traced_delta > 0, "traced dispatch never engaged"
    assert engine_delta == 0, (
        f"traced collectives leaked {engine_delta} bytes into the "
        "engine data plane — XLA owns the traced wire")
    assert frames_delta == 0, (
        f"traced collectives moved {frames_delta} frames on the "
        "engine's data channels")
    checks = {"rank": hvd.rank(), "bytes": int(x.nbytes),
              "traced_ops": int(traced_delta),
              "engine_delta": int(engine_delta),
              "data_frames_delta": int(frames_delta)}
    hvd.barrier()
    hvd.shutdown()
    return checks


def worker_hier():
    """Two-level hierarchical allreduce over a SIMULATED 2-host x
    2-slot topology (distinct HOROVOD_HOSTNAME per host): intra-host
    legs ride shm, inter-host legs ride tcp, across every cross
    schedule — slice-parallel, leader over per-pair rings, leader over
    the per-HOST arena, and compressed leader-arena. Each leg gets its
    own per-transport accounting contract:

    * slice / leader_rings: global shm conservation — every ring byte
      one rank wrote (headers included), its co-located peer consumed;
    * leader_arena (and its bf16 twin — arena legs ship full-width BY
      DESIGN, so the closed form is codec-independent): EXACT per-rank
      shm deltas per op — a member deposits its vector once (C bytes
      sent) and copies the bcast out (C recv); the leader reads every
      member's slot while reducing in place ((L-1)·C recv) and makes
      the bcast deposit (C sent). No shared-result hop, no leader
      deposit, no copy-out — the leg's whole point;
    * leader_arena_bf16: `wire_bytes_saved_total{codec="bf16"}` equals
      the closed-form INTER-HOST savings — the leaders' segmented
      cross ring sends 2(n_cross-1) chunks of COUNT/n_cross elems per
      op at 2 bytes saved per elem; members save nothing (their bytes
      never meet a wire).
    """
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    L = 2                      # launched as 2 hosts x 2 slots
    is_leader = hvd.rank() % L == 0
    expect_bytes = 0
    os.environ["HOROVOD_RING_THRESHOLD"] = "0"
    c_bytes = COUNT * 4

    def snap():
        return hvd.metrics()["metrics"]

    def shm(s, d):
        return s.get('horovod_transport_bytes_total'
                     f'{{direction="{d}",transport="shm"}}', 0)

    legs = [
        ("slice", {"HOROVOD_HIERARCHICAL_MODE": "slice",
                   "HOROVOD_HIER_ARENA": "off"}),
        ("leader_rings", {"HOROVOD_HIERARCHICAL_MODE": "leader",
                          "HOROVOD_HIER_ARENA": "off"}),
        ("leader_arena", {"HOROVOD_HIERARCHICAL_MODE": "leader",
                          "HOROVOD_HIER_ARENA": "auto"}),
        ("leader_arena_bf16", {"HOROVOD_HIERARCHICAL_MODE": "leader",
                               "HOROVOD_HIER_ARENA": "auto",
                               "HOROVOD_WIRE_COMPRESSION": "bf16",
                               "HOROVOD_WIRE_COMPRESSION_MIN_BYTES":
                                   "0"}),
    ]
    deltas = {}
    for name, env in legs:
        os.environ.update(env)
        hvd.barrier()
        before = snap()
        for i in range(ITERS):
            # rank+1 is exactly representable in bf16, so the
            # compressed leg's correctness assert needs no tolerance.
            x = np.full(COUNT, float(hvd.rank() + 1), np.float32)
            out = np.asarray(hvd.allreduce(
                x, name=f"ph.{name}.{i}", op=hvd.Sum))
            assert float(out[0]) == sum(range(1, n + 1)), (name, out[0])
            expect_bytes += x.nbytes
        hvd.barrier()
        after = snap()
        deltas[name] = {
            "sent": shm(after, "sent") - shm(before, "sent"),
            "recv": shm(after, "recv") - shm(before, "recv"),
            "saved": (after.get(
                'horovod_wire_bytes_saved_total{codec="bf16"}', 0)
                - before.get(
                    'horovod_wire_bytes_saved_total{codec="bf16"}', 0)),
            "arena_ops": (after.get("horovod_hier_arena_ops_total", 0)
                          - before.get("horovod_hier_arena_ops_total",
                                       0)),
        }
        os.environ["HOROVOD_WIRE_COMPRESSION"] = "none"

    # Per-pair-ring legs move nothing through the arena — but their
    # intra-host bytes MUST ride shm (a silent tcp fallback would make
    # the conservation assert below pass vacuously at 0 == 0).
    assert deltas["slice"]["arena_ops"] == 0, deltas["slice"]
    assert deltas["leader_rings"]["arena_ops"] == 0, deltas["leader_rings"]
    assert deltas["slice"]["sent"] > 0, deltas["slice"]
    assert deltas["leader_rings"]["sent"] > 0, deltas["leader_rings"]
    # Arena-legged leader: exact per-rank shm byte accounting (arena
    # counters carry no frame headers — deposits count as sent,
    # copy-outs as recv).
    for name in ("leader_arena", "leader_arena_bf16"):
        d = deltas[name]
        assert d["arena_ops"] == ITERS, (name, d)
        want_sent = ITERS * c_bytes
        want_recv = ITERS * ((L - 1) * c_bytes if is_leader else c_bytes)
        assert d["sent"] == want_sent, (name, d, want_sent)
        assert d["recv"] == want_recv, (name, d, want_recv)
    # Compressed leg: closed-form INTER-HOST savings only.
    n_cross = n // L
    want_saved = (ITERS * 2 * (n_cross - 1) * (COUNT // n_cross) * 2
                  if is_leader else 0)
    assert deltas["leader_arena_bf16"]["saved"] == want_saved, (
        deltas["leader_arena_bf16"], want_saved)
    assert deltas["leader_arena"]["saved"] == 0, deltas["leader_arena"]

    snap_end = snap()
    got = snap_end["horovod_allreduce_bytes_total"]
    assert got == expect_bytes, (
        f"allreduce_bytes_total drifted (hier): got {got}, "
        f"expected exactly {expect_bytes}")
    tcp_sent = snap_end.get(
        'horovod_transport_bytes_total{direction="sent",transport="tcp"}',
        0)
    assert tcp_sent > 0, "inter-host legs never rode tcp"
    checks = {"rank": hvd.rank(), "bytes": got,
              "ring_sent": deltas["slice"]["sent"]
              + deltas["leader_rings"]["sent"],
              "ring_recv": deltas["slice"]["recv"]
              + deltas["leader_rings"]["recv"],
              "arena_sent": deltas["leader_arena"]["sent"]
              + deltas["leader_arena_bf16"]["sent"],
              "saved": deltas["leader_arena_bf16"]["saved"]}
    hvd.shutdown()
    return checks


def worker_zero():
    """ZeRO-mode smoke (docs/running.md "ZeRO sharded optimizer
    state"): np=4 eager ``DistributedOptimizer(zero=1)`` steps with
    EXACT per-rank byte accounting on BOTH collective legs:

    * gradient leg: one grouped allreduce of the raw leaves per step,
      so `horovod_allreduce_bytes_total` grows by exactly
      ITERS x sum(leaf nbytes) per rank;
    * update leg: one allgather of this rank's updated segment plus
      the 1-element sentinel (empty shards must still gather), so
      `horovod_allgather_bytes_total` grows by exactly
      ITERS x (hi - lo + 1) x itemsize — (lo, hi) from the SAME
      element-block cut the optimizer uses (`_eager_cut`), so the
      assert pins the ownership math, not a re-derivation.

    Integer-valued gradients make the reduction exact, so the updates
    must be BITWISE equal to a local replicated adam control, and the
    `horovod_optimizer_state_bytes` gauges must show the measured
    sharded footprint at ~1/n of the replicated one."""
    import functools

    import numpy as np

    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.optim.zero import _eager_cut

    hvd.init()
    n = hvd.size()
    rank = hvd.rank()

    rng = np.random.RandomState(7)
    params = {
        "w": rng.randn(311, 17).astype(np.float32),
        "b": rng.randn(63).astype(np.float32),
        "emb": rng.randn(5000).astype(np.float32),
    }
    total = sum(v.size for v in params.values())
    lo, hi = _eager_cut(total, 4, n)[rank]

    inner = optax.adam(1e-3)
    tx = hvd.DistributedOptimizer(inner, zero=1)
    state = tx.init(params)
    ctl_state = inner.init(params)
    ctl_params = {k: v.copy() for k, v in params.items()}

    def snap():
        return hvd.metrics()["metrics"]

    before = snap()
    for i in range(ITERS):
        # rank-dependent INTEGER grads: the ring sum is exact in fp32
        # and /n is dyadic, so the zero path must match the local
        # replicated control bitwise — no tolerance.
        grads = {k: (np.int32(1) + np.arange(v.size, dtype=np.int32)
                     % 7 + rank + i).astype(np.float32).reshape(v.shape)
                 for k, v in params.items()}
        upd, state = tx.update(grads, state, params)
        mean = {k: functools.reduce(
            np.add, [(grads[k] - rank) + r for r in range(n)]) / n
            for k in grads}
        ctl_upd, ctl_state = inner.update(mean, ctl_state, ctl_params)
        for k in upd:
            assert np.array_equal(np.asarray(upd[k]),
                                  np.asarray(ctl_upd[k])), (
                f"zero update diverged from replicated control on {k!r}")
    hvd.barrier()
    after = snap()

    itemsize = 4  # fp32 accumulator — every param leaf is fp32
    want_ar = ITERS * total * itemsize
    got_ar = (after.get("horovod_allreduce_bytes_total", 0)
              - before.get("horovod_allreduce_bytes_total", 0))
    assert got_ar == want_ar, (
        f"zero gradient-leg accounting drifted: allreduce moved "
        f"{got_ar} bytes, closed form says exactly {want_ar}")
    want_ag = ITERS * (hi - lo + 1) * itemsize
    got_ag = (after.get("horovod_allgather_bytes_total", 0)
              - before.get("horovod_allgather_bytes_total", 0))
    assert got_ag == want_ag, (
        f"zero update-leg accounting drifted: allgather moved "
        f"{got_ag} bytes, closed form (segment {hi - lo} elems + "
        f"sentinel) says exactly {want_ag}")

    sharded = after.get(
        'horovod_optimizer_state_bytes{mode="sharded"}', 0)
    replicated = after.get(
        'horovod_optimizer_state_bytes{mode="replicated"}', 0)
    measured = sum(np.asarray(l).nbytes
                   for l in jax.tree.leaves(state.inner))
    assert sharded == measured, (sharded, measured)
    assert replicated > 0 and sharded < replicated / (n - 1), (
        f"sharded state {sharded} B is not ~1/{n} of the replicated "
        f"{replicated} B")
    checks = {"rank": rank, "allreduce_bytes": got_ar,
              "allgather_bytes": got_ag, "segment": [int(lo), int(hi)],
              "state_sharded": int(sharded),
              "state_replicated": int(replicated)}
    hvd.shutdown()
    return checks


def main_zero():
    """The ci.sh `perf_smoke zero` leg: np=4 eager ZeRO with exact
    two-leg byte accounting (its own leg so a zero-path regression
    names itself in CI output)."""
    import json

    from horovod_tpu.runner import run

    results = run(worker_zero, np=4, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "120",
        "HOROVOD_TRANSPORT": "auto",
    })
    assert len(results) == 4, results
    # Every rank saw the same gradient-leg bytes; segments tile [0,
    # total) without overlap.
    assert all(r["allreduce_bytes"] == results[0]["allreduce_bytes"]
               for r in results), results
    segs = sorted(r["segment"] for r in results)
    assert segs[0][0] == 0, segs
    assert all(segs[i][1] == segs[i + 1][0]
               for i in range(len(segs) - 1)), segs
    total_state = sum(r["state_sharded"] for r in results)
    print("perf smoke OK (zero):", results)
    print(json.dumps({
        "metric": "perf_smoke_zero",
        "allreduce_bytes": results[0]["allreduce_bytes"],
        "allgather_bytes": [r["allgather_bytes"] for r in results],
        "state_sharded_total": total_state,
        "state_replicated": results[0]["state_replicated"],
    }))


def main():
    import json

    from horovod_tpu.runner import run

    results = run(worker, np=2, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "60",
        # Explicit pin: the default transport is `auto` now, and this
        # stage's sendmsg/segment counters assert the raw socket plane.
        "HOROVOD_TRANSPORT": "tcp",
    })
    assert len(results) == 2, results
    assert all(r["bytes"] == results[0]["bytes"] for r in results), results
    print("perf smoke OK (tcp):", results)

    # Compression stage: tcp pinned (the per-transport shrink assert
    # compares raw socket bytes), codec engaged via env on every rank
    # (only rank 0's matters — the codec id rides the wire).
    cmp_results = run(worker_compression, np=2, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "60",
        "HOROVOD_TRANSPORT": "tcp",
    })
    assert len(cmp_results) == 2, cmp_results
    assert all(r["bytes"] == cmp_results[0]["bytes"]
               for r in cmp_results), cmp_results
    print("perf smoke OK (compression):", cmp_results)

    # Traced stage: pinned tcp (the data-channel frame counters assert
    # the socket plane), 2 forced CPU devices per worker for the local
    # mesh. Proves the metrics.md claim: traced collectives do NOT ride
    # horovod_allreduce_bytes_total — XLA owns that wire.
    traced_results = run(worker_traced, np=2, extra_env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "60",
        "HOROVOD_TRANSPORT": "tcp",
    })
    assert len(traced_results) == 2, traced_results
    assert all(r["engine_delta"] == 0 and r["traced_ops"] > 0
               for r in traced_results), traced_results
    print("perf smoke OK (traced):", traced_results)

    # Deliberately NO HOROVOD_TRANSPORT here: this stage doubles as the
    # default-route assertion — on a co-located mesh the `auto` default
    # must select shm on its own (worker_shm fails if no data byte ever
    # rode shared memory).
    shm_results = run(worker_shm, np=2, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TCP_TIMEOUT_SECONDS": "60",
    })
    assert len(shm_results) == 2, shm_results
    assert all(r["bytes"] == shm_results[0]["bytes"]
               for r in shm_results), shm_results
    # Exact shm conservation: every byte (headers included) one rank
    # wrote into a ring or arena, its peer consumed.
    total_sent = sum(r["shm_sent"] for r in shm_results)
    total_recv = sum(r["shm_recv"] for r in shm_results)
    assert total_sent == total_recv, (
        f"shm byte conservation broken: sent {total_sent} != "
        f"recv {total_recv}")
    print("perf smoke OK (shm):", shm_results)

    # The simulated hosts are spawned locally: the LAUNCHER consults
    # HVDRUN_FORCE_LOCAL from its own env (extra_env only reaches the
    # workers).
    os.environ["HVDRUN_FORCE_LOCAL"] = "1"
    hier_results = run(worker_hier, np=4, hosts="hostA:2,hostB:2",
                       extra_env={
                           "JAX_PLATFORMS": "cpu",
                           "HOROVOD_CYCLE_TIME": "1",
                           "HOROVOD_TCP_TIMEOUT_SECONDS": "120",
                           "HOROVOD_TRANSPORT": "auto",
                           "HOROVOD_HIERARCHICAL_ALLREDUCE": "auto",
                           "HVDRUN_FORCE_LOCAL": "1",
                       })
    assert len(hier_results) == 4, hier_results
    assert all(r["bytes"] == hier_results[0]["bytes"]
               for r in hier_results), hier_results
    # Ring-legged legs conserve shm bytes globally; the arena legs'
    # exact (deliberately non-conserving) closed form was asserted
    # per rank inside the worker.
    assert (sum(r["ring_sent"] for r in hier_results)
            == sum(r["ring_recv"] for r in hier_results)), hier_results
    print("perf smoke OK (hier):", hier_results)
    print(json.dumps({
        "metric": "perf_smoke",
        "tcp_bytes": results[0]["bytes"],
        "shm_bytes": shm_results[0]["bytes"],
        "shm_conserved": total_sent,
        "hier_bytes": hier_results[0]["bytes"],
        "hier_wire_saved": sum(r["saved"] for r in hier_results),
        "traced_ops": sum(r["traced_ops"] for r in traced_results),
        "traced_engine_bytes_delta": sum(
            r["engine_delta"] for r in traced_results),
    }))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "zero":
        main_zero()
    else:
        main()
