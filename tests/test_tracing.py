"""Tracing-plane tests (ISSUE 6): flight-recorder ring semantics, span
nesting and trace-id scoping, the wire-carried trace id, 2-engine
merged-trace correlation, post-mortem dumps on an injected sever, and
the clock-offset alignment math."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from horovod_tpu.common import telemetry, tracing
from horovod_tpu.common.fault_injection import Rule, get_injector
from horovod_tpu.common.message import Response, ResponseList, ResponseType
from horovod_tpu.engine.engine import Engine
from horovod_tpu.utils import clock


# ---------------------------------------------------------------------------
# flight recorder: ring overwrite + drop accounting

def test_ring_overwrite_and_drop_accounting():
    reg = telemetry.MetricsRegistry()
    rec = tracing.SpanRecorder(4, registry=reg)
    for i in range(10):
        rec.append(i, f"e{i}", "cat", 1000 + i, 5, "t")
    assert rec.depth() == 4
    assert rec.dropped == 6
    snap = rec.snapshot()
    assert [e[2] for e in snap] == ["e6", "e7", "e8", "e9"]  # oldest first
    # seq is monotonic and survives the wrap
    assert [e[0] for e in snap] == [6, 7, 8, 9]
    # The drop counter advances amortized at trim time (the hot path is
    # a lock-free append); it never exceeds the exact property.
    key = 'horovod_trace_events_dropped_total{source="recorder"}'
    assert 0 < reg.snapshot()[key] <= rec.dropped


def test_batch_since_is_incremental_and_nondestructive():
    rec = tracing.SpanRecorder(8)
    for i in range(5):
        rec.append(0, f"a{i}", "c", i, 1, "t")
    evs, cur = rec.batch_since(0)
    assert len(evs) == 5 and cur == 5
    evs2, cur2 = rec.batch_since(cur)
    assert evs2 == [] and cur2 == 5
    rec.append(0, "late", "c", 9, 1, "t")
    evs3, _ = rec.batch_since(cur)
    assert [e[2] for e in evs3] == ["late"]
    assert rec.depth() == 6  # collection never consumes the ring


def test_batch_since_drains_backlog_across_pushes():
    # A backlog bigger than one batch must drain oldest-first over
    # successive calls — never silently skip the old events while the
    # drop counter stays at zero (the truncated trace would read as
    # complete).
    rec = tracing.SpanRecorder(16)
    for i in range(10):
        rec.append(0, f"a{i}", "c", i, 1, "t")
    evs, cur = rec.batch_since(0, limit=4)
    assert [e[0] for e in evs] == [0, 1, 2, 3] and cur == 4
    evs, cur = rec.batch_since(cur, limit=4)
    assert [e[0] for e in evs] == [4, 5, 6, 7] and cur == 8
    evs, cur = rec.batch_since(cur, limit=4)
    assert [e[0] for e in evs] == [8, 9] and cur == 10
    evs, cur = rec.batch_since(cur, limit=4)
    assert evs == [] and cur == 10


def test_zero_capacity_disables_everything():
    tr = tracing.Tracer(capacity=0)
    assert not tr.enabled
    with tr.span("x"):
        pass
    tr.emit("y", "c", 0, 1)
    assert tr.recorder.depth() == 0
    assert tr.status()["enabled"] is False


# ---------------------------------------------------------------------------
# span nesting + trace-id scope

def test_span_nesting_and_trace_scope():
    tr = tracing.Tracer(capacity=64, registry=telemetry.MetricsRegistry())
    with tracing.trace_scope(7):
        with tr.span("outer", cat="exec"):
            time.sleep(0.002)
            with tr.span("inner", cat="xfer"):
                time.sleep(0.001)
    assert tracing.current_trace() == 0  # scope restored
    evs = tr.recorder.snapshot()
    by_name = {e[2]: e for e in evs}
    inner, outer = by_name["inner"], by_name["outer"]
    # both inherited the scope id; inner nests inside outer in time
    assert inner[1] == 7 and outer[1] == 7
    assert outer[4] <= inner[4]
    assert inner[4] + inner[5] <= outer[4] + outer[5]
    # same thread -> same lane in the rendered trace
    assert inner[6] == outer[6]


def test_explicit_trace_id_overrides_scope():
    tr = tracing.Tracer(capacity=8, registry=telemetry.MetricsRegistry())
    with tracing.trace_scope(5):
        tr.emit("e", "c", 0, 1, trace_id=9)
    assert tr.recorder.snapshot()[0][1] == 9


# ---------------------------------------------------------------------------
# wire-carried trace id

def test_response_trace_id_wire_round_trip():
    r = Response(ResponseType.ALLREDUCE, ["t"], channel=2,
                 trace_id=1234567890123)
    r2, _ = Response.deserialize(r.serialize())
    assert r2.trace_id == 1234567890123
    assert r2.channel == 2
    rl = ResponseList([r, Response(ResponseType.BARRIER, trace_id=4)],
                      shutdown=True)
    rl2 = ResponseList.deserialize(rl.serialize())
    assert [x.trace_id for x in rl2.responses] == [1234567890123, 4]
    assert rl2.shutdown


# ---------------------------------------------------------------------------
# clock-offset alignment math

def test_estimate_offset_recovers_known_skew():
    # Peer clock runs D ns ahead; symmetric one-way delay d.
    D, d = 1_000_000_000, 50_000
    a0 = 10_000                      # our stamp, echoed by the peer
    b_recv = a0 + d + D              # peer receives it (peer clock)
    b1 = b_recv + 123_456            # peer holds, then sends its beat
    a1 = (b1 - D) + d                # we receive (our clock)
    off, rtt = tracing.estimate_offset(b1, a0, b_recv, a1)
    assert rtt == 2 * d
    assert off == D                  # exact under symmetric delay


def test_estimate_offset_asymmetry_bounded_by_rtt():
    # Asymmetric delays: error is bounded by rtt/2 (the NTP bound).
    D, d_out, d_back = 777_777, 10_000, 90_000
    a0 = 0
    b_recv = a0 + d_out + D
    b1 = b_recv + 1_000
    a1 = (b1 - D) + d_back
    off, rtt = tracing.estimate_offset(b1, a0, b_recv, a1)
    assert rtt == d_out + d_back
    assert abs(off - D) <= rtt // 2


def test_wall_anchor_offset_same_process_is_zero():
    a = clock.anchor_meta()
    assert tracing.wall_anchor_offset(a, a) == 0
    # A process whose monotonic clock started 5s "later" relative to
    # the same wall clock reads 5s behind: offset = -5s.
    b = dict(a, mono_anchor_ns=a["mono_anchor_ns"] - 5_000_000_000)
    assert tracing.wall_anchor_offset(b, a) == -5_000_000_000
    assert tracing.wall_anchor_offset(None, a) == 0


# ---------------------------------------------------------------------------
# collector dedup + rendering

def test_collector_dedups_overlapping_batches_and_renders_lanes():
    col = tracing.TraceCollector(size=2, capacity=16)
    evs = [(i, 2, f"e{i}", "exec", 1000 + i, 5, "thr", None)
           for i in range(4)]
    col.ingest(1, evs[:3], anchor=clock.anchor_meta())
    col.ingest(1, evs)  # overlap: only the new event lands
    assert col.status() == {"1": 4}
    col.ingest(0, [(0, 2, "mine", "exec", 1000, 5, "thr", None)],
               anchor=clock.anchor_meta())
    doc = tracing.render_chrome(
        col.segments({}, clock.anchor_meta()),
        base_ns=clock.MONO_ANCHOR_NS)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert all(e["args"]["trace_id"] == 2 for e in xs)
    lanes = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert len(lanes) == 2


# ---------------------------------------------------------------------------
# 2-engine merged-trace correlation (in-process harness)

def _start_engines(n=2, cycle_s=0.001):
    from horovod_tpu.backend.threaded import ThreadedGroup

    group = ThreadedGroup(n)
    regs = [telemetry.MetricsRegistry() for _ in range(n)]
    engines = [Engine(rank=r, size=n, backend=group.backend(r),
                      registry=regs[r]) for r in range(n)]
    for e in engines:
        e.cycle_time_s = cycle_s
        e.start()
    return engines, regs


def _all(engines, fn, timeout=60):
    outs = [None] * len(engines)
    errs = [None] * len(engines)

    def w(r):
        try:
            outs[r] = fn(engines[r], r)
        except BaseException as e:  # noqa: BLE001
            errs[r] = e

    ts = [threading.Thread(target=w, args=(r,)) for r in range(len(engines))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    assert all(e is None for e in errs), errs
    return outs


def test_two_engine_merged_trace_shares_ids(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS_SYNC_SECONDS", "0.05")
    engines, _ = _start_engines(2)
    try:
        def work(eng, r):
            for i in range(6):
                eng.synchronize(eng.enqueue_allreduce(
                    np.ones(16, np.float32), name=f"w{i}"), timeout=30)
                time.sleep(0.03)

        _all(engines, work)
        time.sleep(0.2)
        # Flush round: the final batches ride this gather.
        _all(engines, lambda e, r: e.synchronize(
            e.enqueue_allreduce(np.ones(4, np.float32), name="fin"),
            timeout=30))
        doc = engines[0].render_trace()
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} >= {0, 1}
        ids = {p: {e["args"]["trace_id"] for e in xs
                   if e["pid"] == p and str(e["name"]).startswith("exec.")
                   and e["args"]["trace_id"]}
               for p in (0, 1)}
        shared = ids[0] & ids[1]
        assert len(shared) >= 4, (len(ids[0]), len(ids[1]), len(shared))
        # Each shared id covers every kind of span on some rank:
        names = {e["name"] for e in xs
                 if e["args"]["trace_id"] in shared}
        assert any(n.startswith("exec.allreduce") for n in names), names
        assert "queue.dwell" in names, names
        # /status trace view
        st = engines[0].status()
        assert st["trace"]["enabled"] and st["trace"]["depth"] > 0
        assert set(st["trace"]["collected"]) >= {"0", "1"}
    finally:
        _all(engines, lambda e, r: e.shutdown(), timeout=90)


def test_cached_replay_ids_match_across_ranks(monkeypatch):
    """Steady-state (cache fast path) collectives exchange no
    per-response bytes — their trace ids come from the deterministic
    replay sequence and still must agree across ranks."""
    engines, _ = _start_engines(2)
    try:
        seen = [[] for _ in range(2)]
        orig = Engine._perform_operation

        def spy(self, resp):
            if resp.response_type == ResponseType.ALLREDUCE:
                seen[self.rank].append(resp.trace_id)
            return orig(self, resp)

        monkeypatch.setattr(Engine, "_perform_operation", spy)

        def work(eng, r):
            for i in range(8):
                eng.synchronize(eng.enqueue_allreduce(
                    np.ones(8, np.float32), name="steady"), timeout=30)

        _all(engines, work)
        assert seen[0] and seen[0] == seen[1]
        # Replays (odd ids) engaged after the first negotiation (even).
        assert seen[0][0] % 2 == 0
        assert any(t % 2 == 1 for t in seen[0])
        assert len(set(seen[0])) == len(seen[0])  # fresh id per step
    finally:
        _all(engines, lambda e, r: e.shutdown(), timeout=90)


# ---------------------------------------------------------------------------
# post-mortem dump on an injected sever (real TCP mesh)

def _tcp_engines(scope, monkeypatch, n=2):
    from horovod_tpu.backend.rendezvous import RendezvousClient
    from horovod_tpu.backend.tcp import TcpBackend
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    monkeypatch.setenv("HVDRUN_FORCE_LOCAL", "1")
    server = RendezvousServer()
    port = server.start()
    rdv = RendezvousClient("127.0.0.1", port)
    backends = [None] * n
    errs = []

    def build(rank):
        try:
            backends[rank] = TcpBackend(rank, n, rendezvous=rdv, scope=scope)
        except BaseException as e:  # pragma: no cover - bootstrap bug
            errs.append(e)

    ts = [threading.Thread(target=build, args=(r,)) for r in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs
    regs = [telemetry.MetricsRegistry() for _ in range(n)]
    engines = [Engine(rank=r, size=n, backend=backends[r], registry=regs[r])
               for r in range(n)]
    for e in engines:
        e.cycle_time_s = 0.002
    errs2 = []

    def start(e):
        try:
            e.start()
        except BaseException as exc:  # pragma: no cover - init bug
            errs2.append(exc)

    ts = [threading.Thread(target=start, args=(e,)) for e in engines]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs2, errs2
    return server, engines


def test_post_mortem_dump_on_injected_sever(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_HEARTBEAT_INTERVAL_SECONDS", "0")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, engines = _tcp_engines("t_trace_pm", monkeypatch)
    inj = get_injector()
    try:
        # Healthy rounds first (spans in every recorder).
        def warm(eng, r):
            for i in range(3):
                eng.synchronize(eng.enqueue_allreduce(
                    np.ones(8, np.float32), name=f"w{i}"), timeout=30)

        _all(engines, warm)
        # Sever every future exchange with rank 1's socket to rank 0.
        inj.install([Rule(action="sever", peer=0)])

        def failing(eng, r):
            with pytest.raises(Exception):
                for i in range(10):
                    eng.synchronize(eng.enqueue_allreduce(
                        np.ones(8, np.float32), name=f"f{i}"), timeout=30)

        _all(engines, failing)
        # Dumps are written at latch; the stitch runs in rank 0's
        # background-loop teardown, which shutdown() joins below.
        _all(engines, lambda e, r: e.shutdown(), timeout=90)
        flights = sorted(p.name for p in tmp_path.iterdir()
                         if p.name.startswith("flight_rank"))
        assert flights == ["flight_rank0.json", "flight_rank1.json"], flights
        d1 = json.load(open(tmp_path / "flight_rank1.json"))
        assert d1["rank"] == 1 and d1["events"], d1.get("reason")
        assert "peer 0" in d1["reason"] or "rank" in d1["reason"]
        assert "mono_anchor_ns" in d1["anchor"]
        pm = json.load(open(tmp_path / "postmortem.json"))
        meta = pm["horovod_postmortem"]
        assert meta["ranks"] == [0, 1]
        assert meta["verdict"], meta
        assert {e["pid"] for e in pm["traceEvents"]
                if e.get("ph") == "X"} >= {0, 1}
    finally:
        inj.clear()
        server.stop()


def test_no_dump_without_trace_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("HOROVOD_TRACE_DIR", raising=False)
    from horovod_tpu.backend.local import LocalBackend
    from horovod_tpu.common.exceptions import HorovodInternalError

    eng = Engine(rank=0, size=1, backend=LocalBackend(),
                 registry=telemetry.MetricsRegistry())
    eng.cycle_time_s = 0.001
    eng.start()
    try:
        eng._latch_fatal(HorovodInternalError("boom"))
        assert eng.tracer.last_dump is None
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# satellites: /status trace view on a single engine, straggler gauges

def test_status_trace_view_single_engine():
    from horovod_tpu.backend.local import LocalBackend

    reg = telemetry.MetricsRegistry()
    eng = Engine(rank=0, size=1, backend=LocalBackend(), registry=reg)
    eng.cycle_time_s = 0.001
    eng.start()
    try:
        eng.synchronize(eng.enqueue_allreduce(
            np.ones(4, np.float32), name="x"), timeout=30)
        tr = eng.status()["trace"]
        assert tr["enabled"] and tr["buffer_events"] > 0
        assert tr["depth"] > 0 and tr["dropped"] == 0
    finally:
        eng.shutdown()


def test_straggler_gauges_name_the_last_rank(monkeypatch):
    engines, regs = _start_engines(2)
    try:
        # Up to 3 attempts: the scenario depends on rank 0's request
        # genuinely arriving first, and on a loaded 2-core CI box the
        # scheduler can occasionally delay rank 0's enqueue past rank
        # 1's deliberate 0.25s lag — that inversion is box noise, not
        # a gauge bug. Each attempt uses a fresh tensor name, so the
        # gauges re-stamp from a fresh negotiation.
        for attempt in range(3):
            barrier = threading.Barrier(2)

            def work(eng, r, a=attempt):
                barrier.wait()
                if r == 1:
                    time.sleep(0.25)  # rank 1 is deliberately late
                eng.synchronize(eng.enqueue_allreduce(
                    np.ones(8, np.float32), name=f"lag.{a}"), timeout=30)

            _all(engines, work)
            snap = regs[0].snapshot()
            w1 = snap['horovod_negotiation_wait_seconds{rank="1"}']
            w0 = snap['horovod_negotiation_wait_seconds{rank="0"}']
            if (snap["horovod_straggler_rank"] == 1
                    and w1 > 0.15 and w0 == 0.0):
                break
        assert snap["horovod_straggler_rank"] == 1, snap.get(
            "horovod_straggler_rank")
        assert w1 > 0.15 and w0 == 0.0, (w0, w1)
    finally:
        _all(engines, lambda e, r: e.shutdown(), timeout=90)
