"""Fault-tolerant data plane: injection harness, bounded I/O, and clean
failure propagation (ISSUE 1; ref model: the reference's elastic
contract — every collective failure surfaces as HorovodInternalError,
horovod/common/exceptions.py:17-31).

Fast tests (tier-1): rule parsing, injector verdicts, bounded recv,
TcpBackend error translation, engine fail-all propagation, stall
inspector verdicts. The subprocess chaos test (kill 1 of 4 workers
mid-step) is marked `slow`.
"""
import logging
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from horovod_tpu.common import fault_injection
from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    TransportError,
)
from horovod_tpu.common.fault_injection import (
    DROP,
    PASS,
    FaultInjector,
    InjectedFault,
    Rule,
    parse_spec,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_injector():
    """Every test starts and ends with a disarmed process-wide injector."""
    fault_injection.injector.clear()
    yield
    fault_injection.injector.clear()


# ---------------------------------------------------------------------------
# rule grammar
def test_parse_spec_full_grammar():
    rules = parse_spec(
        "kill:step=5;sever:peer=0:after=3;drop:peer=2:rank=1;"
        "delay:peer=1:secs=0.25:op=recv"
    )
    assert [r.action for r in rules] == ["kill", "sever", "drop", "delay"]
    assert rules[0].step == 5
    assert rules[1].peer == 0 and rules[1].after == 3
    assert rules[2].rank == 1
    assert rules[3].secs == 0.25 and rules[3].op == "recv"


@pytest.mark.parametrize("bad", [
    "explode:peer=1",          # unknown action
    "sever:peer",              # field without '='
    "kill",                    # kill needs step=N
    "delay:peer=1",            # delay needs secs=S
    "sever:op=sideways:peer=1",  # bad op
    "drop:peer=1:op=recv",     # drop is send-only; reject, don't no-op
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_spec(bad)


def test_env_spec_arms_injector(monkeypatch):
    monkeypatch.setenv(fault_injection.ENV_VAR, "sever:peer=1")
    inj = FaultInjector()
    inj._load_env()
    assert inj.active
    with pytest.raises(InjectedFault):
        inj.check_io(rank=0, peer=1, op="send")


# ---------------------------------------------------------------------------
# injector verdicts
def test_sever_after_n_frames():
    inj = FaultInjector()
    inj.install([Rule(action="sever", peer=1, after=2)])
    assert inj.check_io(0, 1, "send") == PASS
    assert inj.check_io(0, 1, "send") == PASS
    with pytest.raises(InjectedFault):
        inj.check_io(0, 1, "send")
    # other peers unaffected
    assert inj.check_io(0, 2, "send") == PASS


def test_drop_and_rank_scoping():
    inj = FaultInjector()
    inj.install([Rule(action="drop", peer=0, rank=1)])
    assert inj.check_io(1, 0, "send") == DROP
    assert inj.check_io(2, 0, "send") == PASS  # different rank
    # drop is send-only: a recv neither drops...
    assert inj.check_io(1, 0, "recv") == PASS


def test_drop_after_counts_sends_only():
    inj = FaultInjector()
    inj.install([Rule(action="drop", peer=0, after=2)])
    # ...nor advances the after=K hit counter.
    assert inj.check_io(0, 0, "recv") == PASS
    assert inj.check_io(0, 0, "recv") == PASS
    assert inj.check_io(0, 0, "send") == PASS   # hit 1
    assert inj.check_io(0, 0, "send") == PASS   # hit 2
    assert inj.check_io(0, 0, "send") == DROP   # hit 3 > after=2


def test_delay_sleeps():
    inj = FaultInjector()
    inj.install([Rule(action="delay", peer=0, secs=0.15)])
    t0 = time.monotonic()
    assert inj.check_io(0, 0, "send") == PASS
    assert time.monotonic() - t0 >= 0.15


def test_connect_rules_need_explicit_op():
    inj = FaultInjector()
    inj.install([Rule(action="sever", peer=1)])
    # data-plane default: connect is untouched...
    assert inj.check_io(0, 1, "connect") == PASS
    inj.install([Rule(action="sever", peer=1, op="connect")])
    with pytest.raises(InjectedFault):
        inj.check_io(0, 1, "connect")
    # ...and a connect-scoped rule leaves send/recv alone.
    assert inj.check_io(0, 1, "send") == PASS


def test_kill_rule_fires_at_step():
    """kill:step=N must down the process exactly at step N (subprocess:
    os._exit is unfakeable in-process)."""
    prog = textwrap.dedent("""
        import os
        os.environ["HOROVOD_FAULT_INJECT"] = "kill:step=3"
        from horovod_tpu.common import fault_injection
        for i in range(10):
            fault_injection.advance_step()
            print("survived", i + 1, flush=True)
    """)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop(fault_injection.ENV_VAR, None)
    proc = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert proc.stdout.splitlines() == ["survived 1", "survived 2"]


# ---------------------------------------------------------------------------
# bounded recv + translation
def test_recv_exact_bounded_times_out():
    from horovod_tpu.backend.tcp import _recv_exact_bounded

    a, b = socket.socketpair()
    try:
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="HOROVOD_TCP_TIMEOUT"):
            _recv_exact_bounded(a, 8, timeout=0.4, poll=0.05)
        assert time.monotonic() - t0 < 2.0  # bounded, not hung
    finally:
        a.close()
        b.close()


def test_recv_exact_bounded_detects_peer_close():
    from horovod_tpu.backend.tcp import _recv_exact_bounded

    a, b = socket.socketpair()
    try:
        b.close()
        with pytest.raises(ConnectionError):
            _recv_exact_bounded(a, 8, timeout=0.0, poll=0.05)
    finally:
        a.close()


def _tcp_pair(scope, monkeypatch):
    """Two real TcpBackends full-meshed through a local rendezvous."""
    from horovod_tpu.backend.rendezvous import RendezvousClient
    from horovod_tpu.backend.tcp import TcpBackend
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    monkeypatch.setenv("HVDRUN_FORCE_LOCAL", "1")
    # Pin the raw socket plane: the default transport is `auto` (shm
    # engages between co-located ranks), and this helper feeds the
    # tcp-only suites — fault injections on socket paths, exact
    # tcp byte/frame counter assertions.
    monkeypatch.setenv("HOROVOD_TRANSPORT", "tcp")
    server = RendezvousServer()
    port = server.start()
    rdv = RendezvousClient("127.0.0.1", port)
    backends = [None, None]
    errs = []

    def build(rank):
        try:
            backends[rank] = TcpBackend(rank, 2, rendezvous=rdv, scope=scope)
        except BaseException as e:  # pragma: no cover - bootstrap bug
            errs.append(e)

    threads = [threading.Thread(target=build, args=(r,)) for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs, errs
    assert backends[0] is not None and backends[1] is not None
    return server, backends


def test_tcp_dead_peer_translates_to_transport_error(monkeypatch):
    """A peer whose sockets die mid-collective must surface as
    TransportError (⊂ HorovodInternalError) on the survivor — never a
    raw ConnectionError (the elastic contract, exceptions.py:4-9)."""
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, (b0, b1) = _tcp_pair("t_dead_peer", monkeypatch)
    try:
        b1.shutdown()  # rank 1 "dies": OS closes its sockets
        with pytest.raises(TransportError, match="peer 1"):
            b0.gather_bytes(b"x")  # rank 0 recvs from rank 1
        # the failed peer is severed: later ops fail fast, same type
        with pytest.raises(TransportError):
            b0.gather_bytes(b"x")
    finally:
        b0.shutdown()
        server.stop()


def test_tcp_injected_sever_translates(monkeypatch):
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, (b0, b1) = _tcp_pair("t_sever", monkeypatch)
    try:
        fault_injection.injector.install(
            [Rule(action="sever", peer=1, rank=0, op="recv")]
        )
        with pytest.raises(TransportError, match="severed"):
            b0.gather_bytes(b"x")
    finally:
        fault_injection.injector.clear()
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_tcp_timeout_on_silent_peer(monkeypatch):
    """A peer that is alive but never sends must trip the bounded recv
    within HOROVOD_TCP_TIMEOUT_SECONDS — the hang this PR exists to
    kill."""
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "0.5")
    server, (b0, b1) = _tcp_pair("t_silent", monkeypatch)
    try:
        t0 = time.monotonic()
        with pytest.raises(TransportError, match="no progress"):
            b0.recv_from(1)
        assert time.monotonic() - t0 < 2.0
    finally:
        b0.shutdown()
        b1.shutdown()
        server.stop()


# ---------------------------------------------------------------------------
# engine: fail ALL pending handles, latch terminal state
class _FailingBackend:
    """LocalBackend shape whose data plane dies like a broken mesh."""

    rank, size = 0, 1
    local_rank, local_size, cross_rank, cross_size = 0, 1, 0, 1
    hierarchical = hier_allgather = False

    def set_topology(self, *a):
        pass

    def gather_bytes(self, payload):
        return [payload]

    def bcast_bytes(self, payload):
        return payload

    def allreduce_words(self, words, op):
        return list(words)

    def barrier(self):
        pass

    def allreduce(self, arr, op=None):
        raise TransportError("rank 0: send to peer 1 failed: injected")

    def allgatherv(self, arr, first_dims):
        raise TransportError("rank 0: send to peer 1 failed: injected")

    def broadcast(self, arr, root):
        raise TransportError("rank 0: send to peer 1 failed: injected")

    def alltoallv(self, arr, splits):
        raise TransportError("rank 0: send to peer 1 failed: injected")

    def adasum_allreduce_all(self, arr):
        raise TransportError("rank 0: send to peer 1 failed: injected")

    def shutdown(self):
        pass


def test_engine_transport_error_fails_all_pending_and_latches():
    from horovod_tpu.engine.engine import Engine

    eng = Engine(rank=0, size=1, backend=_FailingBackend())
    eng.start()
    try:
        h1 = eng.enqueue_allreduce(np.ones(4, np.float32), name="a")
        h2 = eng.enqueue_allreduce(np.ones(4, np.float32), name="b")
        with pytest.raises(HorovodInternalError, match="peer 1"):
            eng.synchronize(h1, timeout=30)
        with pytest.raises(HorovodInternalError, match="peer 1"):
            eng.synchronize(h2, timeout=30)
        # The engine is dead: a NEW enqueue must fail immediately with
        # the latched reason, not park forever.
        h3 = eng.enqueue_allreduce(np.ones(4, np.float32), name="c")
        with pytest.raises(HorovodInternalError, match="peer 1"):
            eng.synchronize(h3, timeout=30)
    finally:
        eng.shutdown()


def test_tensor_queue_finalize_latches_status():
    from horovod_tpu.common.message import Request
    from horovod_tpu.common.types import Status, StatusType
    from horovod_tpu.engine.tensor_queue import TensorQueue, TensorTableEntry

    q = TensorQueue()
    q.finalize(Status.Aborted("mesh down"))
    st = q.add_to_tensor_queue(
        TensorTableEntry(tensor_name="t", tensor=None), Request()
    )
    assert st.type == StatusType.ABORTED and "mesh down" in st.reason


# ---------------------------------------------------------------------------
# stall inspector (satellite: the abort path had no direct test)
@pytest.fixture
def _hvd_log_capture():
    records = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = _Cap(level=logging.DEBUG)
    lg = logging.getLogger("horovod_tpu")
    lg.addHandler(h)
    yield records
    lg.removeHandler(h)


def _make_inspector(monkeypatch, warn="0.05", shut="0"):
    monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", warn)
    monkeypatch.setenv("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", shut)
    from horovod_tpu.engine.stall import StallInspector

    insp = StallInspector(size=2)
    insp.last_check = 0.0  # open the rate gate for the first check()
    return insp


def test_stall_warning_emitted_once(monkeypatch, _hvd_log_capture):
    insp = _make_inspector(monkeypatch)
    insp.record("allreduce.g", 0)  # rank 1 never shows up
    time.sleep(0.08)
    assert insp.check() is None  # warn, not abort
    warnings = [r for r in _hvd_log_capture
                if "Stalled op: allreduce.g" in r.getMessage()]
    assert len(warnings) == 1
    assert "[missing ranks: [1]]" in warnings[0].getMessage()
    insp.last_check = 0.0
    assert insp.check() is None  # second check: already warned, no spam
    assert len([r for r in _hvd_log_capture
                if "Stalled op" in r.getMessage()]) == 1


def test_stall_shutdown_verdict(monkeypatch):
    insp = _make_inspector(monkeypatch, warn="0.01", shut="0.05")
    insp.record("allreduce.g", 0)
    time.sleep(0.08)
    reason = insp.check()
    assert reason is not None and "stall shutdown" in reason
    assert "allreduce.g" in reason and "[1]" in reason


def test_stall_remove_clears_warned_state(monkeypatch, _hvd_log_capture):
    insp = _make_inspector(monkeypatch)
    insp.record("allreduce.g", 0)
    time.sleep(0.08)
    insp.check()
    assert "allreduce.g" in insp.warned
    insp.remove("allreduce.g")
    assert not insp.pending and "allreduce.g" not in insp.warned
    # the op comes back (next batch) and stalls again -> fresh warning
    insp.record("allreduce.g", 0)
    time.sleep(0.08)
    insp.last_check = 0.0
    insp.check()
    assert len([r for r in _hvd_log_capture
                if "Stalled op" in r.getMessage()]) == 2


def test_stall_disabled_never_aborts(monkeypatch):
    monkeypatch.setenv("HOROVOD_STALL_CHECK_DISABLE", "1")
    monkeypatch.setenv("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", "0.01")
    from horovod_tpu.engine.stall import StallInspector

    insp = StallInspector(size=2)
    insp.record("allreduce.g", 0)
    insp.last_check = 0.0
    time.sleep(0.05)
    assert insp.check() is None


# ---------------------------------------------------------------------------
# fault injection x the zero-copy/pipelined I/O paths: sever mid-segment,
# delay on the persistent sender queue, timeout during recv_into. Every
# failure must still surface as TransportError (⊂ HorovodInternalError,
# the class the engine's fail-all-pending path keys on — covered by
# test_engine_transport_error_fails_all_pending_and_latches above).
def _ring_pair_allreduce(b0, b1, count=8192):
    """Drive a 2-rank ring allreduce on real TCP backends; returns
    (results, errors) without raising so callers can assert on the
    failure mode."""
    results, errors = [None, None], [None, None]

    def w(i, b):
        try:
            x = np.arange(count, dtype=np.float32) * (i + 1)
            results[i] = b.allreduce(x)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    ts = [threading.Thread(target=w, args=(i, b))
          for i, b in ((0, b0), (1, b1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    return results, errors


def test_sever_mid_segment_raises_transport_error(monkeypatch):
    """A sever that fires on the Nth frame lands MID-CHUNK on the
    segmented pipelined path (each ring step is several frames): the
    persistent sender's ticket must carry it back as TransportError."""
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    # 8192 floats / 2 ranks = 16KB chunks; 4KB segments -> 4 frames per
    # step, so after=2 fires mid-chunk.
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "4096")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, (b0, b1) = _tcp_pair("t_sever_seg", monkeypatch)
    try:
        fault_injection.injector.install(
            [Rule(action="sever", rank=0, peer=1, op="send", after=2)]
        )
        results, errors = _ring_pair_allreduce(b0, b1)
        # rank 0 fails with TransportError: either the severed send's
        # ticket surfaces first, or its concurrent recv on the (now
        # hard-closed) socket does — both translate cleanly.
        assert isinstance(errors[0], TransportError), errors
        # rank 0's socket to peer 1 is hard-closed: fail fast afterwards
        with pytest.raises(TransportError):
            b0.send_to(1, b"x")
    finally:
        fault_injection.injector.clear()
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_sever_mid_segment_fails_the_exact_ticket(monkeypatch):
    """Driving the segmented send path directly: segment 3 of 4 hits the
    sever rule, and ITS ticket carries the translated error while the
    first two segments completed."""
    import numpy as np_

    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, (b0, b1) = _tcp_pair("t_sever_ticket", monkeypatch)
    try:
        fault_injection.injector.install(
            [Rule(action="sever", rank=0, peer=1, op="send", after=2)]
        )
        seg = np_.arange(1024, dtype=np_.float32)
        tickets = [b0.send_async(1, seg) for _ in range(4)]
        for _ in range(2):  # the two pre-sever segments arrive intact
            assert len(b1.recv_from(0)) == seg.nbytes
        tickets[0].wait()
        tickets[1].wait()
        with pytest.raises(TransportError, match="severed"):
            tickets[2].wait()
        # everything queued behind the sever fails too (peer gone)
        with pytest.raises(TransportError):
            tickets[3].wait()
    finally:
        fault_injection.injector.clear()
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_delay_on_persistent_sender_queue(monkeypatch):
    """A delay rule sleeps inside the persistent sender worker: the
    queued frame is late but correct, and the caller only feels the
    delay at ticket wait / recv time."""
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "10")
    server, (b0, b1) = _tcp_pair("t_delay_sender", monkeypatch)
    try:
        fault_injection.injector.install(
            [Rule(action="delay", rank=0, peer=1, op="send", secs=0.3)]
        )
        t0 = time.monotonic()
        ticket = b0.send_async(1, b"payload")  # returns immediately
        enqueue_dt = time.monotonic() - t0
        assert enqueue_dt < 0.25, f"send_async blocked {enqueue_dt:.2f}s"
        data = b1.recv_from(0)
        ticket.wait()
        assert bytes(data) == b"payload"
        assert time.monotonic() - t0 >= 0.3  # the worker slept
    finally:
        fault_injection.injector.clear()
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_timeout_during_recv_into(monkeypatch):
    """A silent peer must trip the bounded recv_into within
    HOROVOD_TCP_TIMEOUT_SECONDS — the zero-copy path keeps the
    dead-peer heartbeat."""
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "0.5")
    server, (b0, b1) = _tcp_pair("t_silent_into", monkeypatch)
    try:
        buf = np.zeros(64, np.float32)
        t0 = time.monotonic()
        with pytest.raises(TransportError, match="no progress"):
            b0.recv_into_from(1, buf)
        assert time.monotonic() - t0 < 2.0
        # the timed-out peer is severed: fail fast, same type
        with pytest.raises(TransportError):
            b0.recv_into_from(1, buf)
    finally:
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_timeout_mid_frame_during_recv_into(monkeypatch):
    """A peer that sends a frame header then goes silent: recv_into is
    already parked on the payload and must still respect the idle
    deadline."""
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "0.5")
    server, (b0, b1) = _tcp_pair("t_half_frame", monkeypatch)
    try:
        import struct as _struct

        # Raw header promising 1024 bytes, then silence.
        b1.peers[0].sendall(_struct.pack("<Q", 1024))
        buf = bytearray(1024)
        with pytest.raises(TransportError, match="no progress"):
            b0.recv_into_from(1, buf)
    finally:
        b0.shutdown()
        b1.shutdown()
        server.stop()


def test_drop_on_pipelined_send_hangs_peer_into_timeout(monkeypatch):
    """A dropped segment means the receiver's recv_into starves: it
    must fail via the bounded timeout, not hang."""
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "4096")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "1")
    server, (b0, b1) = _tcp_pair("t_drop_seg", monkeypatch)
    try:
        fault_injection.injector.install(
            [Rule(action="drop", rank=0, peer=1, op="send", after=1)]
        )
        results, errors = _ring_pair_allreduce(b0, b1)
        assert isinstance(errors[1], TransportError), errors
        # Either the starved recv's own idle timeout fires, or the
        # other rank times out first and its sever delivers a FIN —
        # both are clean bounded TransportError failures.
        assert ("no progress" in str(errors[1])
                or "closed connection" in str(errors[1])), errors
    finally:
        fault_injection.injector.clear()
        b0.shutdown()
        b1.shutdown()
        server.stop()


# ---------------------------------------------------------------------------
# fault injection x the pipelined executor path: a transport death on one
# channel while another channel is mid-collective must fail EVERY pending
# handle on EVERY channel with the transport reason, kill the executors,
# and leave no thread hung (ISSUE 4 satellite).
def _tcp_engines(scope, monkeypatch, nranks=2):
    """Two real Engines over a TCP mesh in one process (the executor
    pool + channel-tagged data plane end to end)."""
    from horovod_tpu.engine.engine import Engine

    server, backends = _tcp_pair(scope, monkeypatch)
    engines = [Engine(rank=r, size=nranks, backend=backends[r])
               for r in range(nranks)]
    for e in engines:
        e.cycle_time_s = 0.001
    start_errs = []

    def _start(e):
        try:
            e.start()
        except BaseException as exc:  # pragma: no cover - init bug
            start_errs.append(exc)

    ts = [threading.Thread(target=_start, args=(e,)) for e in engines]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not start_errs, start_errs
    return server, engines


def _run_pipelined_workload(engines, count=1 << 14, ops=2):
    """Each rank enqueues `ops` allreduces (one response per op with
    fusion disabled -> round-robin over both channels), then waits.
    Returns per-rank lists of results-or-exceptions."""
    out = [[None] * ops for _ in engines]

    def w(i, eng):
        handles = [
            eng.enqueue_allreduce(
                np.full(count, float(i + 1), np.float32), name=f"c{k}")
            for k in range(ops)
        ]
        for k, h in enumerate(handles):
            try:
                out[i][k] = eng.synchronize(h, timeout=60)
            except BaseException as e:  # noqa: BLE001
                out[i][k] = e
    ts = [threading.Thread(target=w, args=(i, e))
          for i, e in enumerate(engines)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    return out


def _shutdown_engines(engines):
    ts = [threading.Thread(target=e.shutdown) for e in engines]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)


def test_sever_on_one_channel_fails_every_channel(monkeypatch):
    """Sever mid-stream with two channels in flight: all pending handles
    on both ranks fail with the transport reason, post-death enqueues
    fail fast, and the executor threads exit — no hang."""
    monkeypatch.setenv("HOROVOD_CHANNEL_POLICY", "rr")
    monkeypatch.setenv("HOROVOD_NUM_CHANNELS", "2")
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1")
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "4096")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "5")
    server, engines = _tcp_engines("t_exec_sever", monkeypatch)
    try:
        # The sever lands partway into the segmented data stream (the
        # delay keeps rank 1's contributions slow enough that both
        # channels are still mid-collective when it fires).
        fault_injection.injector.install([
            Rule(action="delay", rank=1, peer=0, op="send", secs=0.02),
            Rule(action="sever", rank=0, peer=1, op="send", after=15),
        ])
        out = _run_pipelined_workload(engines)
        # Every handle either completed BEFORE the fault landed or
        # failed with the transport reason — never a hang (None /
        # TimeoutError), and the fault must have hit someone.
        failures = 0
        for r, per_rank in enumerate(out):
            for k, res in enumerate(per_rank):
                assert res is not None, (r, k, "synchronize hung")
                assert not isinstance(res, TimeoutError), (r, k, res)
                if isinstance(res, HorovodInternalError):
                    failures += 1
                    assert ("peer" in str(res) or "severed" in str(res)
                            or "shut down" in str(res)), (r, k, res)
                else:
                    assert isinstance(res, np.ndarray), (r, k, res)
        assert failures > 0, out
        # Terminal status latched: a post-death enqueue fails immediately.
        h = engines[0].enqueue_allreduce(
            np.ones(8, np.float32), name="after_death")
        with pytest.raises(HorovodInternalError):
            engines[0].synchronize(h, timeout=30)
    finally:
        fault_injection.injector.clear()
        _shutdown_engines(engines)
        server.stop()
    for eng in engines:
        for ex in eng._executors.values():
            assert not ex.thread.is_alive(), (
                f"rank {eng.rank} channel {ex.channel} executor leaked")


def test_timeout_on_one_channel_fails_every_channel(monkeypatch):
    """A dropped segment starves one channel's recv into the bounded
    timeout; the resulting TransportError must still take down every
    channel's pending handles on both ranks within the bound."""
    monkeypatch.setenv("HOROVOD_CHANNEL_POLICY", "rr")
    monkeypatch.setenv("HOROVOD_NUM_CHANNELS", "2")
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1")
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "4096")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "1")
    server, engines = _tcp_engines("t_exec_drop", monkeypatch)
    try:
        fault_injection.injector.install([
            Rule(action="drop", rank=0, peer=1, op="send", after=15),
        ])
        t0 = time.monotonic()
        out = _run_pipelined_workload(engines)
        assert time.monotonic() - t0 < 60, "not bounded"
        failures = 0
        for r, per_rank in enumerate(out):
            for k, res in enumerate(per_rank):
                assert res is not None, (r, k, "synchronize hung")
                assert not isinstance(res, TimeoutError), (r, k, res)
                if isinstance(res, HorovodInternalError):
                    failures += 1
                else:
                    assert isinstance(res, np.ndarray), (r, k, res)
        assert failures > 0, out
    finally:
        fault_injection.injector.clear()
        _shutdown_engines(engines)
        server.stop()
    for eng in engines:
        for ex in eng._executors.values():
            assert not ex.thread.is_alive()


def test_pipelined_engines_healthy_path_correctness(monkeypatch):
    """Control experiment for the two tests above: the same 2-channel
    TCP engine pair with no fault injected completes correctly."""
    monkeypatch.setenv("HOROVOD_CHANNEL_POLICY", "rr")
    monkeypatch.setenv("HOROVOD_NUM_CHANNELS", "2")
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1")
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "4096")
    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "30")
    server, engines = _tcp_engines("t_exec_ok", monkeypatch)
    try:
        out = _run_pipelined_workload(engines, ops=4)
        for per_rank in out:
            for res in per_rank:
                assert isinstance(res, np.ndarray), res
                np.testing.assert_allclose(res[:4], np.full(4, 3.0))
    finally:
        _shutdown_engines(engines)
        server.stop()


# ---------------------------------------------------------------------------
# chaos: kill 1 of 4 real workers mid-step (the acceptance scenario)
_CHAOS_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import fault_injection
    from horovod_tpu.common.exceptions import HorovodInternalError

    STEPS = int(os.environ.get("TEST_CHAOS_STEPS", "50"))
    hvd.init()
    try:
        for step in range(STEPS):
            out = hvd.allreduce(np.ones(8, np.float32), name="g")
            fault_injection.advance_step()  # doomed rank dies here
        sys.exit(0)
    except HorovodInternalError:
        sys.exit(42)   # the contract: collective failure -> HIE
    except ConnectionError:
        sys.exit(13)   # raw transport error leaked: forbidden
    except Exception:
        sys.exit(14)
""")


@pytest.mark.slow
def test_chaos_kill_one_of_four_workers(tmp_path):
    """Kill 1 of 4 subprocess workers mid-step; every survivor must
    raise HorovodInternalError within 2x HOROVOD_TCP_TIMEOUT_SECONDS of
    the death — no indefinite hang, no raw ConnectionError escaping."""
    from horovod_tpu.runner.hosts import parse_hosts, get_host_assignments
    from horovod_tpu.runner.launch import slot_env
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    timeout_s = 5.0
    np_world = 4
    kill_rank = 2

    server = RendezvousServer()
    port = server.start()
    script = tmp_path / "worker.py"
    script.write_text(_CHAOS_WORKER)

    hosts = parse_hosts(f"localhost:{np_world}")
    slots = get_host_assignments(hosts, np_world)
    procs = {}
    try:
        for slot in slots:
            env = dict(os.environ)
            env.update(slot_env(slot, "127.0.0.1", port))
            env["PYTHONPATH"] = REPO
            env["HVDRUN_FORCE_LOCAL"] = "1"
            env["HOROVOD_CYCLE_TIME"] = "1"
            env["HOROVOD_TCP_TIMEOUT_SECONDS"] = str(timeout_s)
            env.pop("HOROVOD_FAULT_INJECT", None)
            if slot.rank == kill_rank:
                env["HOROVOD_FAULT_INJECT"] = "kill:step=3"
            procs[slot.rank] = subprocess.Popen(
                [sys.executable, str(script)], env=env,
            )
        # The doomed worker exits first (around step 3)...
        t_death = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if procs[kill_rank].poll() is not None:
                t_death = time.monotonic()
                break
            time.sleep(0.1)
        assert t_death is not None, "doomed worker never died"
        assert procs[kill_rank].returncode == 1

        # ...and every survivor must fail CLEANLY within 2x the timeout.
        budget = 2 * timeout_s + 30  # + slack for jax import/teardown
        for rank, proc in procs.items():
            if rank == kill_rank:
                continue
            remaining = budget - (time.monotonic() - t_death)
            try:
                proc.wait(timeout=max(remaining, 1.0))
            except subprocess.TimeoutExpired:
                pytest.fail(f"survivor rank {rank} hung past the bound")
        codes = {r: p.returncode for r, p in procs.items() if r != kill_rank}
        assert all(c == 42 for c in codes.values()), (
            f"survivors must exit via HorovodInternalError (42): {codes}"
        )
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()
