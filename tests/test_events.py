"""Events-plane tests (docs/events.md): the lifecycle ring's bounds and
drop accounting, epoch+step causal stamps, the JSONL spool's torn-tail
tolerance, the fleet fold's deterministic skew-adjusted ordering, every
subsystem emitter, the incident-report merge, the hvdtop frame, and that
a disabled plane does no work per emit."""
import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

from horovod_tpu.common import alerts, drain, goodput, telemetry
from horovod_tpu.common import events, timeseries as ts
from horovod_tpu.common import tracing
from horovod_tpu.common.exceptions import WorkerPreempted
from horovod_tpu.utils import chrome_trace, clock
from horovod_tpu.utils import env as env_cfg


@pytest.fixture(autouse=True)
def _fresh_plane(monkeypatch):
    """Every test starts with a clean singleton and no EVENTS_* env."""
    for var in (env_cfg.EVENTS_BUFFER, env_cfg.EVENTS_DIR,
                env_cfg.EVENTS_SPOOL_SECONDS):
        monkeypatch.delenv(var, raising=False)
        monkeypatch.delenv(var.replace("HOROVOD_", "HVD_TPU_", 1),
                           raising=False)
    events.set_current(None)
    events.set_epoch_provider(None)
    yield
    events.set_current(None)
    events.set_epoch_provider(None)


def _rec(**kw):
    kw.setdefault("registry", telemetry.MetricsRegistry())
    kw.setdefault("capacity", 64)
    kw.setdefault("rank", 0)
    kw.setdefault("spool_dir", "")  # ring only unless a test opts in
    return events.EventRecorder(**kw)


def _ev(seq, rank, wall, epoch=0, step=0, kind="k", sev="info",
        attrs=None):
    """A raw event tuple in the recorder's wire order."""
    return (seq, wall, wall, rank, epoch, step, sev, kind, attrs)


def _load_script(name):
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Ring semantics


def test_ring_bounds_and_drop_counting():
    rec = _rec(capacity=8)
    for i in range(30):
        rec.record("test.tick", attrs={"i": i})
    assert rec.depth() == 8
    assert rec.dropped == 22  # exact: total 30, retained 8
    snap = rec.snapshot()
    assert [e[0] for e in snap] == list(range(22, 30))  # newest, sorted
    # Counters: every record counted; drops counted on (amortized) trim.
    assert rec._m_recorded.value == 30
    assert 0 < rec._m_dropped.value <= rec.dropped
    st = rec.status()
    assert st["enabled"] and st["capacity"] == 8
    assert st["depth"] == 8 and st["dropped"] == 22
    assert "spool" not in st


def test_tail_and_to_dict():
    rec = _rec()
    rec.record("a.one", severity=events.WARN, attrs={"x": 1})
    rec.record("a.two")
    tail = rec.tail(n=8)
    assert [d["kind"] for d in tail] == ["a.one", "a.two"]
    assert tail[0]["sev"] == "warn" and tail[0]["attrs"] == {"x": 1}
    assert "attrs" not in tail[1]  # None attrs elided from dict form
    assert tail[0]["wall_ns"] and tail[0]["mono_ns"]


def test_event_carries_epoch_and_step(monkeypatch):
    monkeypatch.setenv(env_cfg.MESH_SCOPE, "hvd_mesh_e7")
    led = goodput.GoodputLedger(registry=telemetry.MetricsRegistry(),
                                enabled=True, stamp_seconds=0.0)
    with led.step():
        pass
    goodput.set_current(led)
    try:
        rec = _rec()
        ev = rec.record("test.stamped")
        assert ev[4] == 7   # elastic topology epoch from MESH_SCOPE
        assert ev[5] == 1   # the ledger's step cursor
    finally:
        goodput.set_current(None)
    # Outside elastic mode, epoch is -1 and step falls back to 0.
    monkeypatch.delenv(env_cfg.MESH_SCOPE)
    ev = _rec().record("test.static")
    assert ev[4] == -1 and ev[5] == 0
    # A driver process has no MESH_SCOPE: the ElasticDriver installs an
    # epoch provider so its events interleave with the workers'.
    events.set_epoch_provider(lambda: 4)
    assert _rec().record("test.driver")[4] == 4
    events.set_epoch_provider(lambda: None)
    assert _rec().record("test.predriver")[4] == -1


def test_disabled_plane_is_inert(monkeypatch, tmp_path):
    rec = _rec(capacity=0, spool_dir=str(tmp_path))
    assert not rec.enabled
    assert rec.record("test.x") is None
    assert rec.depth() == 0 and rec.dropped == 0
    assert rec._spool_thread is None  # capacity 0 never arms the spool
    assert list(tmp_path.iterdir()) == []
    # And through the singleton emitter, driven by the env knob.
    monkeypatch.setenv(env_cfg.EVENTS_BUFFER, "0")
    assert events.emit("test.y", probe=1) is None
    assert events.active() is not None  # created, but inert
    assert not events.active().enabled


def test_env_knob_parsing(monkeypatch):
    assert env_cfg.events_buffer() == env_cfg.DEFAULT_EVENTS_BUFFER
    assert env_cfg.events_dir() == ""
    assert env_cfg.events_spool_seconds() == \
        env_cfg.DEFAULT_EVENTS_SPOOL_SECONDS
    # The HVD_TPU_ compatibility alias is honored.
    monkeypatch.setenv("HVD_TPU_EVENTS_BUFFER", "7")
    assert env_cfg.events_buffer() == 7
    monkeypatch.setenv(env_cfg.EVENTS_BUFFER, "12")  # canonical wins
    assert env_cfg.events_buffer() == 12
    # A typo must not silently disable the plane.
    monkeypatch.setenv(env_cfg.EVENTS_BUFFER, "bogus")
    monkeypatch.delenv("HVD_TPU_EVENTS_BUFFER")
    assert env_cfg.events_buffer() == env_cfg.DEFAULT_EVENTS_BUFFER
    monkeypatch.setenv(env_cfg.EVENTS_BUFFER, "-5")
    assert env_cfg.events_buffer() == 0
    monkeypatch.setenv(env_cfg.EVENTS_DIR, "/tmp/evj")
    assert env_cfg.events_dir() == "/tmp/evj"
    # Spool cadence: floored (no spinning writer), bogus -> default.
    monkeypatch.setenv(env_cfg.EVENTS_SPOOL_SECONDS, "0")
    assert env_cfg.events_spool_seconds() == 0.05
    monkeypatch.setenv(env_cfg.EVENTS_SPOOL_SECONDS, "nope")
    assert env_cfg.events_spool_seconds() == \
        env_cfg.DEFAULT_EVENTS_SPOOL_SECONDS


def test_batch_since_and_push_cursor():
    rec = _rec()
    for i in range(5):
        rec.record("test.t", attrs={"i": i})
    evs, nxt = rec.batch_since(0)
    assert [e[0] for e in evs] == [0, 1, 2, 3, 4] and nxt == 5
    evs, nxt = rec.batch_since(nxt)
    assert evs == [] and nxt == 5
    push = rec.make_push()
    blob = push()
    assert len(blob["batch"]) == 5
    assert "mono_anchor_ns" in blob["anchor"]
    assert push() is None  # cursor advanced: nothing new
    rec.record("test.more")
    assert len(push()["batch"]) == 1


def test_singleton_emit_and_set_rank():
    rec = _rec(rank=2)
    events.set_current(rec)
    ev = events.emit("test.a", foo=1)
    assert ev[3] == 2 and ev[8] == {"foo": 1}
    events.set_rank(5)  # elastic renumber: later events carry it
    assert events.emit("test.b")[3] == 5
    assert events.emit("test.c", rank=9)[3] == 9  # explicit wins
    assert events.active() is rec


def test_local_view_shapes():
    # No recorder installed -> disabled body (mesh-mode /events before
    # init, or a plane turned off).
    assert events.local_view() == {"local": {"enabled": False}}
    events.set_current(_rec(rank=1))
    events.emit("test.a", foo=1)
    body = events.local_view()
    assert body["local"]["enabled"] and body["local"]["depth"] == 1
    assert body["local"]["events"][0]["kind"] == "test.a"
    assert "fleet" not in body
    events.set_current(events.EventRecorder(capacity=0))
    assert events.local_view() == {"local": {"enabled": False}}


# ---------------------------------------------------------------------------
# Spool: durable JSONL journal


def test_spool_journal_anchor_and_torn_tail(tmp_path):
    rec = _rec(capacity=16, rank=3, spool_dir=str(tmp_path),
               spool_seconds=0.05)
    for i in range(4):
        rec.record("test.spooled", attrs={"i": i})
    rec.flush_spool()
    path = events.journal_path(str(tmp_path), 3)
    assert path.endswith("events_rank3.jsonl")
    assert rec.status()["spool"]["path"] == path
    docs = events.read_journal(path)
    assert [d["attrs"]["i"] for d in docs] == [0, 1, 2, 3]
    assert all(d["rank"] == 3 for d in docs)
    anchor = events.read_anchor(path)
    assert anchor["rank"] == 3 and "wall_anchor_ns" in anchor
    # A hard kill tears the tail line and can corrupt one in the
    # middle — replay must keep every complete event.
    with open(path, "a", encoding="utf-8") as f:
        f.write("not json at all\n")
        f.write('{"kind":"test.torn","seq":9')  # no closing newline
    docs = events.read_journal(path)
    assert len(docs) == 4
    rec.close_spool()
    assert rec._spool_thread is None
    # Driver processes (rank -1) get their own journal name.
    assert events.journal_path("/d", -1).endswith("events_driver.jsonl")


def test_set_current_closes_previous_spool(tmp_path):
    rec = _rec(capacity=8, rank=0, spool_dir=str(tmp_path),
               spool_seconds=0.05)
    rec.record("test.x")
    events.set_current(rec)
    events.set_current(None)  # must drain + stop the writer thread
    assert rec._spool_thread is None
    docs = events.read_journal(events.journal_path(str(tmp_path), 0))
    assert [d["kind"] for d in docs] == ["test.x"]


# ---------------------------------------------------------------------------
# Fleet fold: dedup, determinism, skew alignment


def test_fleet_fold_deterministic_across_ingest_orders():
    r0 = [_ev(i, 0, 1000 + 10 * i, kind=f"a{i}") for i in range(4)]
    r1 = [_ev(i, 1, 1005 + 10 * i, kind=f"b{i}") for i in range(4)]
    fa = events.FleetEvents(2)
    fa.ingest(0, [list(e) for e in r0])
    fa.ingest(1, [list(e) for e in r1])
    fb = events.FleetEvents(2)
    fb.ingest(1, [list(e) for e in r1[:2]])
    fb.ingest(0, [list(e) for e in r0])
    fb.ingest(1, [list(e) for e in r1[2:]])
    fb.ingest(0, [list(e) for e in r0])  # re-pushed batch: deduped
    assert fa.merged() == fb.merged()
    kinds = [d["kind"] for d in fa.merged()]
    assert kinds == ["a0", "b0", "a1", "b1", "a2", "b2", "a3", "b3"]
    snap = fa.snapshot()
    assert snap["ranks"] == [0, 1]
    assert snap["depths"] == {"0": 4, "1": 4}


def test_fleet_fold_epoch_and_step_dominate_wall():
    # A drained at (e3) sorts before the remesh that opened e4, even
    # when the remesh rank's wall clock reads EARLIER.
    f = events.FleetEvents(2)
    f.ingest(0, [list(_ev(0, 0, wall=9_000, epoch=3, step=120,
                          kind="drain.drained"))])
    f.ingest(1, [list(_ev(0, 1, wall=1_000, epoch=4, step=120,
                          kind="elastic.remesh"))])
    assert [d["kind"] for d in f.merged()] == \
        ["drain.drained", "elastic.remesh"]


def test_causal_order_interleaves_stepless_events():
    # Driver-process events carry no step cursor (step 0); they must
    # interleave at their wall position, not sort to the epoch front.
    w1 = {"epoch": 3, "step": 3, "wall_ns": 1000, "rank": 1, "seq": 0,
          "kind": "drain.drained"}
    drv = {"epoch": 3, "step": 0, "wall_ns": 1500, "rank": -1, "seq": 0,
           "kind": "host.quarantine"}
    w2 = {"epoch": 3, "step": 5, "wall_ns": 2000, "rank": 0, "seq": 1,
          "kind": "ckpt.commit"}
    assert [d["kind"] for d in events.causal_order([w2, drv, w1])] == \
        ["drain.drained", "host.quarantine", "ckpt.commit"]
    # A step-less event before any stepped one still leads its epoch.
    init = {"epoch": 3, "step": 0, "wall_ns": 10, "rank": 0, "seq": 0,
            "kind": "engine.init"}
    assert [d["kind"] for d in events.causal_order([w1, init])] == \
        ["engine.init", "drain.drained"]


def test_fleet_skew_alignment():
    SKEW = 5_000_000_000  # rank 1's wall clock runs 5s fast
    local = clock.anchor_meta()
    remote = {"mono_anchor_ns": local["mono_anchor_ns"],
              "wall_anchor_ns": local["wall_anchor_ns"] + SKEW}
    f = events.FleetEvents(2)
    base = local["wall_anchor_ns"]
    # True order: r1's notice fired 1s BEFORE r0's commit; r1's fast
    # wall stamps it 4s after.
    f.ingest(0, [list(_ev(0, 0, wall=base + 2_000_000_000,
                          kind="drain.commit_barrier"))])
    f.ingest(1, [list(_ev(0, 1, wall=base + 1_000_000_000 + SKEW,
                          kind="drain.notice"))], anchor=remote)
    # Without an RTT sample both walls are trusted: skew 0, wrong order.
    assert f.skew_ns(1) == 0
    assert [d["kind"] for d in f.merged()] == \
        ["drain.commit_barrier", "drain.notice"]
    # The health plane's mono offset unlocks the wall-anchor delta.
    f.set_offsets({1: 0})
    assert f.skew_ns(1) == SKEW
    merged = f.merged()
    assert [d["kind"] for d in merged] == \
        ["drain.notice", "drain.commit_barrier"]
    assert merged[0]["adj_wall_ns"] == base + 1_000_000_000
    assert f.snapshot()["skew_ns"]["1"] == SKEW


# ---------------------------------------------------------------------------
# Subsystem emitters (each stamps the ring through the singleton)


def _kinds(rec):
    return [e[7] for e in rec.snapshot()]


def _by_kind(rec, kind):
    return [events.to_dict(e) for e in rec.snapshot() if e[7] == kind]


def test_drain_emitters():
    rec = _rec()
    events.set_current(rec)
    coord = drain.DrainCoordinator()
    coord.set_managed(True)
    try:
        coord.request("test preemption")
        (notice,) = _by_kind(rec, events.DRAIN_NOTICE)
        assert notice["sev"] == "warn"
        assert notice["attrs"] == {"reason": "test preemption",
                                   "managed": True}
        # Survivor side: first commit-barrier observation of a peer
        # drain emits once (not per commit).
        drain._drain_commit(coord, object(), draining=False)
        drain._drain_commit(coord, object(), draining=False)
        assert len(_by_kind(rec, events.DRAIN_COMMIT)) == 2
        assert len(_by_kind(rec, events.DRAIN_PEER)) == 1
        # Draining side: the commit completes the drain.
        with pytest.raises(WorkerPreempted):
            drain._drain_commit(coord, object(), draining=True)
        (drained,) = _by_kind(rec, events.DRAIN_DRAINED)
        assert drained["attrs"]["reason"] == "test preemption"
    finally:
        coord.reset()


def test_alert_emitters():
    rec = _rec()
    events.set_current(rec)
    reg = telemetry.MetricsRegistry()
    rule = alerts.ThresholdRule("hot", "m", threshold=10.0,
                                for_seconds=15.0, clear_seconds=15.0)
    base = time.monotonic()
    st = ts.TimeSeriesStore(64)
    st.add_sample({"m": 25.0}, wall=0, mono=base)
    eng = alerts.AlertEngine(st, reg, rules=[rule], rules_spec="",
                             tracer=None, stale_after=1e9)
    eng.evaluate(st, now=base)
    st.add_sample({"m": 25.0}, wall=16, mono=base + 16)
    eng.evaluate(st, now=base + 16)  # 16s >= for_seconds -> FIRE
    (fire,) = _by_kind(rec, events.ALERT_FIRE)
    assert fire["sev"] == "warn" and fire["attrs"]["rule"] == "hot"
    st.add_sample({"m": 1.0}, wall=20, mono=base + 20)
    eng.evaluate(st, now=base + 20)
    st.add_sample({"m": 1.0}, wall=36, mono=base + 36)
    eng.evaluate(st, now=base + 36)  # 16s below -> resolve
    (clear,) = _by_kind(rec, events.ALERT_CLEAR)
    assert clear["attrs"]["rule"] == "hot"


def test_controller_decision_emitted_on_change_only():
    from horovod_tpu.runner.elastic import controller as ectl

    rec = _rec()
    events.set_current(rec)
    fake = types.SimpleNamespace(rendezvous=types.SimpleNamespace(
        handle_put=lambda key, val: None))
    ctl = ectl.ElasticityController(fake, interval=60.0)
    ctl._publish(ectl.HOLD, 2, 2, "steady state")
    ctl._publish(ectl.HOLD, 2, 2, "steady state")  # same fact: no spam
    assert len(_by_kind(rec, events.CONTROLLER_DECISION)) == 1
    ctl._publish(ectl.SCALE_UP, 4, 2, "2 slots available")
    decs = _by_kind(rec, events.CONTROLLER_DECISION)
    assert len(decs) == 2
    assert decs[0]["sev"] == "info" and decs[0]["rank"] == -1
    assert decs[1]["sev"] == "warn"
    assert decs[1]["attrs"]["action"] == ectl.SCALE_UP
    assert decs[1]["attrs"]["target_np"] == 4


def test_checkpoint_emitters(tmp_path):
    from horovod_tpu.common import checkpoint as ck
    from horovod_tpu.elastic.state import JaxState

    rec = _rec()
    events.set_current(rec)
    st = JaxState(params={"w": np.arange(6, dtype=np.float32)}, batch=1)
    st.save()
    m = ck.CheckpointManager(str(tmp_path), rank=0, size=1,
                             interval_steps=1, commit_timeout=30)
    try:
        assert m.save(st, step=3, blocking=True)
    finally:
        m.stop()
    (commit,) = _by_kind(rec, events.CKPT_COMMIT)
    assert commit["attrs"] == {"ckpt_step": 3, "shards": 1}
    st2 = JaxState(params={"w": np.zeros(6, np.float32)}, batch=0)
    m2 = ck.CheckpointManager(str(tmp_path), rank=0, size=1)
    try:
        assert m2.restore_latest(st2) == 3
    finally:
        m2.stop()
    (restore,) = _by_kind(rec, events.CKPT_RESTORE)
    assert restore["attrs"]["ckpt_step"] == 3
    assert restore["attrs"]["written_world"] == 1


def test_replay_emitter():
    rec = _rec()
    events.set_current(rec)
    led = goodput.GoodputLedger(registry=telemetry.MetricsRegistry(),
                                enabled=True, stamp_seconds=0.0, rank=2)
    for _ in range(2):
        with led.step():
            pass
    led.note_restore()  # rollback to committed (0): both steps lost
    (replay,) = _by_kind(rec, events.CKPT_REPLAY)
    assert replay["sev"] == "warn" and replay["rank"] == 2
    assert replay["attrs"]["lost_steps"] == 2
    assert replay["attrs"]["restored_step"] == 0
    led.note_restore()  # nothing newly lost: no second event
    assert len(_by_kind(rec, events.CKPT_REPLAY)) == 1


def test_serving_swap_emitter(monkeypatch):
    from horovod_tpu.serving import replicas

    rec = _rec()
    events.set_current(rec)
    monkeypatch.setattr(replicas.basics, "rank", lambda: 1)
    rs = replicas.ReplicaSet.__new__(replicas.ReplicaSet)
    rs.weight_step = -1
    rs.loader = types.SimpleNamespace(take=lambda step: {"w": 2})
    rs._m_weight_step = types.SimpleNamespace(set=lambda v: None)
    rs._m_swaps = types.SimpleNamespace(inc=lambda: None)
    rs._commit(5)
    rs._commit(5)  # replayed commit: no swap, no event
    (swap,) = _by_kind(rec, events.SERVING_SWAP)
    assert swap["rank"] == 1 and swap["attrs"]["ckpt_step"] == 5
    assert rs.weight_step == 5


# ---------------------------------------------------------------------------
# Trace integration: lifecycle instants + stitched skew


def test_chrome_instant_helpers():
    d = chrome_trace.instant("drain.notice", 12.5, pid=3,
                             cat="lifecycle", args={"reason": "x"})
    assert d["ph"] == "i" and d["s"] == "p" and d["pid"] == 3
    doc = {"traceEvents": [d, {"ph": "X", "name": "span"}]}
    assert chrome_trace.instant_events(doc) == [d]


def test_stitch_post_mortem_lifecycle_instants_and_skew(tmp_path):
    SKEW = 2_000_000_000
    anchor0 = {"mono_anchor_ns": 1_000, "wall_anchor_ns": 500_000}
    anchor1 = {"mono_anchor_ns": 1_000,
               "wall_anchor_ns": 500_000 + SKEW}

    def _life(rank, mono, kind):
        return {"seq": 0, "wall_ns": mono, "mono_ns": mono,
                "rank": rank, "epoch": 1, "step": 4, "sev": "warn",
                "kind": kind}

    for r, anchor, kind in ((0, anchor0, "drain.commit_barrier"),
                            (1, anchor1, "drain.notice")):
        with open(tracing.flight_path(str(tmp_path), r), "w") as f:
            json.dump({"rank": r, "events": [], "anchor": anchor,
                       "reason": "test",
                       "lifecycle": [_life(r, 5_000 + r, kind)]}, f)
    out = tracing.stitch_post_mortem(str(tmp_path), verdict="drill",
                                     expect_ranks=2, grace_s=0.5,
                                     offsets={0: 0, 1: SKEW})
    with open(out) as f:
        doc = json.load(f)
    pm = doc["horovod_postmortem"]
    assert pm["per_rank"]["1"]["skew_ns"] == SKEW
    assert pm["per_rank"]["0"]["lifecycle_events"] == 1
    inst = {d["name"]: d for d in chrome_trace.instant_events(doc)}
    assert inst["drain.notice"]["pid"] == 1
    assert inst["drain.notice"]["cat"] == "lifecycle"
    assert inst["drain.notice"]["args"]["kind"] == "drain.notice"
    # Rank 1's lane is shifted onto the coordinator timebase.
    base = anchor0["mono_anchor_ns"]
    assert inst["drain.commit_barrier"]["ts"] == (5_000 - base) / 1e3
    assert inst["drain.notice"]["ts"] == (5_001 - SKEW - base) / 1e3


# ---------------------------------------------------------------------------
# scripts/incident_report.py: the merged chronicle


def test_incident_report_merges_journals_with_skew(tmp_path):
    ir = _load_script("incident_report")
    SKEW = 5_000_000_000
    base = 1_000_000_000_000

    def _row(seq, rank, wall, kind, sev="warn", **attrs):
        return {"seq": seq, "wall_ns": wall, "mono_ns": wall,
                "rank": rank, "epoch": 3, "step": 0, "sev": sev,
                "kind": kind, "attrs": attrs or None}

    # Rank 1 (the preempted one) has a wall clock 5s fast; true order:
    # notice(r1) -> commit(r0) -> drained(r1) -> remesh(driver).
    r1 = [_row(0, 1, base + 1_000_000_000 + SKEW, "drain.notice"),
          _row(1, 1, base + 3_000_000_000 + SKEW, "drain.drained")]
    r0 = [_row(0, 0, base + 2_000_000_000, "drain.commit_barrier")]
    drv = [_row(0, -1, base + 4_000_000_000, "elastic.remesh")]
    with open(os.path.join(tmp_path, "events_rank0.jsonl"), "w") as f:
        f.writelines(json.dumps(d) + "\n" for d in r0)
        f.write('{"kind":"torn')  # hard-kill tail: ignored
    with open(os.path.join(tmp_path, "events_rank1.jsonl"), "w") as f:
        f.writelines(json.dumps(d) + "\n" for d in r1)
    with open(os.path.join(tmp_path, "events_driver.jsonl"), "w") as f:
        f.writelines(json.dumps(d) + "\n" for d in drv)
    # A flight dump re-carries r1's first event (deduped) + one unique.
    with open(os.path.join(tmp_path, "flight_rank1.json"), "w") as f:
        json.dump({"rank": 1, "lifecycle": [
            r1[0],
            _row(2, 1, base + 3_500_000_000 + SKEW, "host.quarantine"),
        ]}, f)
    with open(os.path.join(tmp_path, "postmortem.json"), "w") as f:
        json.dump({"horovod_postmortem": {
            "verdict": "rank 1 preempted",
            "per_rank": {"1": {"skew_ns": SKEW}},
        }}, f)

    report = ir.build_report([str(tmp_path)])
    s = report["summary"]
    assert s["events"] == 5
    assert s["ranks"] == [-1, 0, 1]
    assert s["skew_ns"] == {"1": str(SKEW)} or \
        s["skew_ns"] == {"1": SKEW}
    assert s["verdict"] == "rank 1 preempted"
    kinds = [d["kind"] for d in report["events"]]
    # With the skew applied the chronicle reads as one narrative; the
    # raw walls would have sorted every r1 event last.
    assert kinds == ["drain.notice", "drain.commit_barrier",
                     "drain.drained", "host.quarantine",
                     "elastic.remesh"]
    text = ir.render_text(report)
    assert "drain.notice" in text and "rank 1 preempted" in text
    assert "clock skew applied" in text
    # Empty directory: no events, exit code 1.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert ir.main([str(empty)]) == 1


# ---------------------------------------------------------------------------
# scripts/hvdtop.py: one rendered frame


def test_hvdtop_render_frame():
    top = _load_script("hvdtop")
    now = 1_700_000_000.0
    snap = {
        "wall": now,
        "status": {
            "size": 2,
            "goodput": {"steps": 120},
            "checkpoint": {"last_committed_step": 100},
        },
        "goodput": {"fleet": {
            "ranks": {
                "0": {"steps": 120, "goodput_ratio": 0.91,
                      "exposed_comm_seconds": 1.0},
                "1": {"steps": 118, "goodput_ratio": 0.62,
                      "exposed_comm_seconds": 9.5},
            },
            "max_exposed_comm_rank": 1,
        }},
        "alerts": {"fleet": {"firing_by_rule": {"stall": [1]}}},
        "events": {"fleet": {"events": [
            {"epoch": 3, "step": 100, "rank": 1, "sev": "warn",
             "kind": "drain.notice", "attrs": {"reason": "signal"}},
            {"epoch": 3, "step": 100, "rank": 1, "sev": "warn",
             "kind": "drain.drained"},
        ]}},
        "controller": {"wall": now - 30, "action": "scale_down",
                       "current_np": 2, "target_np": 1,
                       "reason": "grant shrank"},
        "grant": 1,
        "drain": {"phase": "requested", "wall": now - 5},
        "kv_epoch": 3,
    }
    frame = top.render(snap)
    assert "world 2" in frame and "epoch 3" in frame
    assert "last commit 100" in frame
    assert "<- max exposed" in frame
    assert "stall (ranks [1])" in frame
    assert "scale_down" in frame and "grant shrank" in frame
    assert "capacity grant: 1 slots" in frame
    assert "DRAIN in flight: phase requested" in frame
    assert "drain.notice" in frame and "reason=signal" in frame
    # Everything down: degrades, never crashes.
    dead = top.render({"wall": now, "status": None, "goodput": None,
                       "alerts": None, "events": None,
                       "controller": None, "grant": None, "drain": None,
                       "kv_epoch": None})
    assert "unreachable" in dead
    assert "no decision published" in dead
    assert "disabled or empty" in dead


# ---------------------------------------------------------------------------
# Overhead, counted and not timed: a disabled plane does no work per
# emit, an enabled one reads the clock once.


def test_emit_overhead_under_two_percent(monkeypatch):
    on = _rec(capacity=4096)
    off = _rec(capacity=0)
    entered = []
    clock_reads = []
    monkeypatch.setattr(off, "record", lambda *a, **kw: entered.append(a))
    real_mono = clock.mono_ns

    def mono_ns():
        clock_reads.append(1)
        return real_mono()

    monkeypatch.setattr(clock, "mono_ns", mono_ns)
    steps = 20

    events.set_current(off)
    assert [events.emit("perf.step", i=i) for i in range(steps)] \
        == [None] * steps
    # Returned before an event was built: the recording path was not
    # entered, no clock was read, nothing was appended or dropped.
    assert entered == [] and clock_reads == []
    assert off.depth() == 0 and off.dropped == 0 and off.snapshot() == []

    events.set_current(on)
    recorded = [events.emit("perf.step", i=i) for i in range(steps)]
    assert [ev[7] for ev in recorded] == ["perf.step"] * steps
    assert len(clock_reads) == steps  # one clock read an event
    assert on.depth() == steps and on.dropped == 0
