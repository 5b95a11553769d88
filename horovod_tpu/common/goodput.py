"""Goodput plane: step-level efficiency accounting with badput
attribution (docs/goodput.md).

The observability stack can say *what* is slow (PR 2 counters, PR 6
spans, PR 10 time-series/alerts) but not *how much training it cost*:
nothing answers "what fraction of wall-clock since job start became
useful steps, and which subsystem ate the rest". This module is that
ledger — the standard goodput/badput accounting used to operate large
training fleets, fed by four sources:

* **Step demarcation** — an ``hvd.step()`` scope (or automatic
  boundaries from ``parallel/step.py``'s ``wrap_step`` calls,
  ``optim/distributed.py``'s update path and ``elastic/state.py``
  commits) marks the edges of productive steps.
  Each completed step emits a ``step`` span into the PR 6 flight
  recorder with its exposed-comm share in the args.

* **Exposed communication** — ``HandleManager.wait`` reports only the
  time the calling (training) thread actually BLOCKED on a collective:
  a handle that completed while compute overlapped it costs ~0 here,
  so overlapped communication never counts as badput.

* **Checkpoint stall** — the durability plane reports the
  training-thread cost of snapshot copies and counts backpressure
  skips (``common/checkpoint.py``).

* **Restart badput** — generation start/stop stamps plus the
  last-committed-step live in a durable ledger stamp (a tiny JSON next
  to the checkpoints, best-effort mirrored to the rendezvous KV), so a
  kill-all restart's downtime AND the steps replayed after restore are
  counted across process lifetimes. Elastic resets bracket their
  disruption window the same way.

Everything left over is compute (goodput); with a declared per-step
flop count (``HOROVOD_STEP_FLOPS``) the ledger also reports achieved
FLOP/s and — against ``HOROVOD_GOODPUT_PEAK_FLOPS`` — MFU.

The per-rank totals ride the existing telemetry piggyback, so rank 0's
``/goodput`` view attributes badput per rank fleet-wide; the series
land in the PR 10 time-series ring (the sampler snapshots the same
registry) and feed the default ``goodput_degraded`` /
``exposed_comm_regression`` alert rules; the failure post-mortem embeds
the ledger next to the flight recorder.

Ledger identity: one ledger per process (it must survive the engine
swap every elastic reset performs), injectable per engine for the
in-process multi-rank test harness — the registry/tracer pattern.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..utils import atomic_file, clock
from ..utils import env as env_cfg
from ..utils.logging import get_logger

logger = get_logger()

STAMP_NAME = "goodput.json"
STAMP_FORMAT = 1
KV_SCOPE = "goodput"
KV_KEY = "status"

# Step-boundary sources, ranked: an explicit hvd.step() scope always
# wins; a `wrap_step` call whose step holds an optimizer update beats
# the update's own marker (the call IS that step's host boundary, so
# its compiled program stages no marker: parallel/step.py); the
# optimizer update path beats elastic commits (a loop doing both would
# otherwise count every step twice). The first boundary from a
# higher-ranked source takes the counter over; lower-ranked boundaries
# are ignored from then on.
_SOURCE_RANK = {"commit": 1, "optim": 2, "wrap_step": 3, "explicit": 4}


class _StepScope:
    """Context manager for one explicit step (``hvd.step()``)."""

    __slots__ = ("_led", "_t0_ns")

    def __init__(self, led: "GoodputLedger"):
        self._led = led

    def __enter__(self):
        self._led._claim_source("explicit")
        self._led._take_exposed_window()  # pre-step waits are not step comm
        self._t0_ns = clock.mono_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # The step body raised (collective failure mid-step): the
            # step never completed, so it must not count — a phantom
            # completed step would inflate the cursor (over-counting
            # replay after the restore) and its partial duration would
            # pollute the mean step time. The exposure window is
            # dropped from step attribution too (the totals keep it).
            self._led._take_exposed_window()
            return False
        self._led._finish_step(self._t0_ns, clock.mono_ns(), "explicit")
        return False


class _NoopScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SCOPE = _NoopScope()


class GoodputLedger:
    """Job-level step/badput accounting for one rank.

    All ``note_*`` entry points are cheap (a float add under a lock)
    and safe to call from any thread; the heavier stamp persistence is
    rate-limited and rank-0-only."""

    def __init__(self, registry=None, tracer=None, rank: int = 0,
                 stamp_path: Optional[str] = None, kv=None,
                 enabled: Optional[bool] = None,
                 step_flops: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 stamp_seconds: Optional[float] = None):
        if registry is None:
            from . import telemetry

            registry = telemetry.default_registry()
        self.registry = registry
        self.tracer = tracer
        self.rank = rank
        self.enabled = (env_cfg.goodput_enabled() if enabled is None
                        else enabled)
        self.step_flops = (env_cfg.step_flops() if step_flops is None
                           else step_flops)
        self.peak_flops = (env_cfg.goodput_peak_flops()
                           if peak_flops is None else peak_flops)
        self.stamp_seconds = (env_cfg.goodput_stamp_seconds()
                              if stamp_seconds is None else stamp_seconds)
        self.stamp_path = stamp_path
        self._kv = kv
        self._lock = threading.Lock()
        # Generation identity: this process lifetime. The durable stamp
        # carries the FIRST generation's start, so wall-clock spans the
        # whole job across restarts.
        self.gen_start_wall = time.time()
        self.gen_start_mono = time.monotonic()
        self.generation = 1
        self.job_start_wall = self.gen_start_wall
        # Cumulative accounting (prior lifetimes folded in at load).
        self.steps = 0              # steps completed this process
        self.prior_steps = 0
        self.step_seconds = 0.0
        self.prior_step_seconds = 0.0
        # Steps whose duration was actually measured: the first
        # boundary after a reset closes a step whose start was never
        # seen — it counts (the committed-step cursor must track
        # commits 1:1) but must not dilute the mean step time the
        # replay estimate uses.
        self.timed_steps = 0
        self.prior_timed_steps = 0
        self.exposed_seconds = 0.0
        self.prior_exposed_seconds = 0.0
        self.stall_seconds = 0.0
        self.prior_stall_seconds = 0.0
        self.step_stall_seconds = 0.0
        self.prior_step_stall_seconds = 0.0
        self.stall_skips = 0
        # Exposed comm attributed to a completed step's window — the
        # share subtracted from goodput. Out-of-step waits (initial
        # broadcast, eval collectives between explicit scopes, sync
        # during a re-mesh) still count in the exposed TOTAL but live
        # in other/downtime wall time, so subtracting them from step
        # compute would double-count the loss.
        self.step_exposed_seconds = 0.0
        self.prior_step_exposed_seconds = 0.0
        self.downtime_seconds = 0.0     # restart + elastic-reset badput
        self.prior_downtime_seconds = 0.0
        # Announced-preemption badput (docs/fault_tolerance.md): drain
        # windows and their re-mesh, kept apart from `downtime_seconds`
        # (the *failure* bucket) — a fleet losing time to spot reclaims
        # needs different remediation than one losing time to crashes.
        self.preempt_seconds = 0.0
        self.prior_preempt_seconds = 0.0
        self.replayed_steps = 0
        self.prior_replayed_steps = 0
        self.replay_seconds = 0.0
        self.prior_replay_seconds = 0.0
        # Global step cursor: continues from the prior lifetime's stamp
        # so replay after a kill-all restore is computable.
        self.current_step = 0
        self.committed_step = 0
        # Auto-boundary state.
        self._source_rank = 0
        self._boundary_ns: Optional[int] = None
        # Exposed-comm accumulated since the last step edge (the
        # per-step attribution window). A single shared window — waits
        # land on the training thread in every supported loop shape.
        self._window_exposed = 0.0
        self._window_stall = 0.0
        # Disruption bracket (elastic reset / restore in progress).
        self._disrupt_t0: Optional[float] = None
        self._disrupt_reason = ""
        self._disrupt_bucket = "failure"
        self._last_stamp_mono = 0.0
        # Stamp ownership is fixed at construction: only the ORIGINAL
        # rank 0 (the one that loaded the stamp) may write it. A
        # survivor promoted to rank 0 by elastic renumbering never
        # loaded the job history, so letting it write would overwrite
        # the job-lifetime ledger with fresh-lifetime numbers.
        self._stamp_owner = (rank == 0)
        # KV mirror rides a lazy daemon worker (latest-doc-wins): a
        # down rendezvous server must stall the mirror, never the
        # training thread the stamp is written from.
        self._kv_doc: Optional[dict] = None
        self._kv_cond = threading.Condition()
        self._kv_thread: Optional[threading.Thread] = None
        # -- telemetry (docs/metrics.md "Goodput plane") ---------------
        self._m_steps = registry.counter(
            "horovod_goodput_steps_total",
            "Training steps demarcated by the goodput ledger")
        # The same steps by the source that drove them, made on a
        # source's first step (docs/metrics.md).
        self._m_source_steps = {}
        self._m_step_s = registry.histogram(
            "horovod_goodput_step_seconds",
            "Wall duration of demarcated training steps")
        self._m_exposed_step_s = registry.histogram(
            "horovod_exposed_comm_step_seconds",
            "Exposed (training-thread-blocking) communication per step")
        self._m_exposed = registry.counter(
            "horovod_exposed_comm_seconds_total",
            "Seconds the training thread blocked on collective handles "
            "(overlapped communication never counts)")
        self._m_stall = registry.counter(
            "horovod_ckpt_stall_seconds_total",
            "Training-thread seconds lost to checkpoint snapshot copies")
        self._m_downtime = registry.counter(
            "horovod_restart_downtime_seconds_total",
            "Seconds of job downtime: kill-all restart gaps plus "
            "elastic reset/restore windows")
        self._m_preempt = registry.counter(
            "horovod_preemption_badput_seconds_total",
            "Seconds of job downtime attributed to announced "
            "preemptions (graceful drains + their re-mesh), kept apart "
            "from the failure bucket")
        self._m_replayed = registry.counter(
            "horovod_replayed_steps_total",
            "Steps re-executed after a restore (work done twice)")
        self._m_replay_s = registry.counter(
            "horovod_replay_seconds_total",
            "Estimated wall seconds of replayed steps (steps x mean "
            "step time)")
        self._m_generation = registry.gauge(
            "horovod_goodput_generation",
            "Process lifetimes of this job recorded by the ledger")
        self._m_generation.set(1)
        self._m_ratio = registry.gauge(
            "horovod_goodput_ratio",
            "Fraction of job wall-clock spent in productive step "
            "compute (NaN before the first completed step)")
        self._m_ratio.set_function(self._ratio_or_nan)
        if (self.enabled and self.rank == 0
                and (self.stamp_path or self._kv is not None)):
            self._load_stamp()

    # -- durable stamps (rank 0) ---------------------------------------
    def _read_stamp_doc(self) -> Optional[dict]:
        """The newest available stamp: the file on shared storage, or
        — when the file is gone but the rendezvous KV survived (an
        elastic-only restart whose stamp dir was lost) — the KV
        mirror. The mirror is the read fallback, not just a dashboard
        row."""
        if self.stamp_path:
            try:
                with open(self.stamp_path) as f:
                    return json.load(f)
            except (OSError, ValueError):
                pass
        if self._kv is not None:
            try:
                raw = self._kv.get(KV_SCOPE, KV_KEY)
                if raw:
                    return json.loads(raw.decode())
            except Exception:
                pass
        return None

    def _load_stamp(self):
        doc = self._read_stamp_doc()
        if doc is None or doc.get("format") != STAMP_FORMAT:
            return
        now = time.time()
        self.job_start_wall = float(doc.get("job_start_wall",
                                            self.gen_start_wall))
        self.generation = int(doc.get("generation", 0)) + 1
        self._m_generation.set(self.generation)
        # The gap since the previous lifetime's last stamp is restart
        # downtime: the job existed (its ledger says so) but made no
        # progress. Granularity = the stamp cadence. A stamp released
        # by a graceful drain (`draining`) means the previous lifetime
        # ended by ANNOUNCED preemption, so its gap belongs in the
        # preemption bucket, not the failure bucket.
        gap = max(now - float(doc.get("stamp_wall", now)), 0.0)
        if doc.get("draining"):
            self.preempt_seconds += gap
            self._m_preempt.inc(gap)
        else:
            self.downtime_seconds += gap
            self._m_downtime.inc(gap)
        self.prior_steps = int(doc.get("steps", 0))
        self.prior_step_seconds = float(doc.get("step_seconds", 0.0))
        self.prior_timed_steps = int(doc.get("timed_steps", 0))
        self.prior_exposed_seconds = float(doc.get("exposed_seconds", 0.0))
        self.prior_step_exposed_seconds = float(
            doc.get("step_exposed_seconds", 0.0))
        self.prior_stall_seconds = float(doc.get("stall_seconds", 0.0))
        self.prior_step_stall_seconds = float(
            doc.get("step_stall_seconds", 0.0))
        self.prior_downtime_seconds = float(doc.get("downtime_seconds", 0.0))
        self.prior_preempt_seconds = float(doc.get("preempt_seconds", 0.0))
        self.prior_replayed_steps = int(doc.get("replayed_steps", 0))
        self.prior_replay_seconds = float(doc.get("replay_seconds", 0.0))
        self.current_step = int(doc.get("current_step", 0))
        self.committed_step = int(doc.get("committed_step", 0))
        # Carry the demarcation source: replay accounting after a
        # durable restore must know whether the step cursor counts
        # COMMITS (then a manifest step is comparable) or finer-grained
        # optimizer/explicit steps (then it is not — see note_restore).
        self._source_rank = int(doc.get("source_rank", 0))
        logger.info(
            "goodput ledger resumed: generation %d, %.1fs restart "
            "downtime since the previous stamp, step cursor %d "
            "(committed %d)", self.generation, gap, self.current_step,
            self.committed_step)

    def _stamp_doc(self) -> dict:
        return {
            "format": STAMP_FORMAT,
            "job_start_wall": self.job_start_wall,
            "generation": self.generation,
            "stamp_wall": time.time(),
            "steps": self.prior_steps + self.steps,
            "step_seconds": self.prior_step_seconds + self.step_seconds,
            "timed_steps": self.prior_timed_steps + self.timed_steps,
            "exposed_seconds": (self.prior_exposed_seconds
                                + self.exposed_seconds),
            "step_exposed_seconds": (self.prior_step_exposed_seconds
                                     + self.step_exposed_seconds),
            "stall_seconds": self.prior_stall_seconds + self.stall_seconds,
            "step_stall_seconds": (self.prior_step_stall_seconds
                                   + self.step_stall_seconds),
            "downtime_seconds": (self.prior_downtime_seconds
                                 + self.downtime_seconds),
            "preempt_seconds": (self.prior_preempt_seconds
                                + self.preempt_seconds),
            "replayed_steps": self.prior_replayed_steps + self.replayed_steps,
            "replay_seconds": (self.prior_replay_seconds
                               + self.replay_seconds),
            "current_step": self.current_step,
            "committed_step": self.committed_step,
            "source_rank": self._source_rank,
        }

    def release_stamp(self) -> bool:
        """Graceful-drain handoff, owner side (docs/goodput.md "Stamp
        handoff"): force one final stamp marked ``draining`` — written
        synchronously to both the file and the KV mirror, because this
        process is about to exit and the lazy mirror worker may never
        get another turn. The mark does two jobs: a follow-up lifetime
        attributes its restart gap to the *preemption* bucket, and a
        survivor promoted to rank 0 may adopt stamp ownership
        (``try_adopt_stamp``) instead of durable accounting dying with
        the drained process."""
        if not self.enabled or self.rank != 0 or not self._stamp_owner:
            return False
        self._last_stamp_mono = time.monotonic()
        doc = self._stamp_doc()
        doc["draining"] = True
        if self.stamp_path:
            try:
                os.makedirs(os.path.dirname(self.stamp_path) or ".",
                            exist_ok=True)
                atomic_file.atomic_write_text(
                    self.stamp_path, json.dumps(doc), fsync=False)
            except OSError as e:
                logger.warning("goodput release stamp write failed: %s", e)
        if self._kv is not None:
            try:
                self._kv.put(KV_SCOPE, KV_KEY,
                             json.dumps(doc, separators=(",", ":")).encode())
            except Exception:
                pass
        logger.info("goodput: stamp released at step %d (draining)",
                    self.committed_step)
        return True

    def try_adopt_stamp(self) -> bool:
        """Graceful-drain handoff, survivor side: a worker promoted to
        rank 0 by elastic renumbering adopts the durable ledger IF the
        previous owner released it (a ``draining`` stamp). The released
        totals become this ledger's prior lifetimes and its OWN window
        is dropped — the released stamp already accounts the same job
        wall-clock from the rank-0 view, so keeping both would double-
        count. Without a released stamp this is a no-op: an unreleased
        stamp means the owner may still be alive, and overwriting the
        job ledger with fresh-lifetime numbers is exactly what
        construction-time ownership exists to prevent."""
        if not self.enabled or self._stamp_owner:
            return False
        if self.stamp_path is None:
            self.stamp_path = _default_stamp_path()
        if self._kv is None:
            self._kv = _kv_from_env()
        doc = self._read_stamp_doc()
        if (doc is None or doc.get("format") != STAMP_FORMAT
                or not doc.get("draining")):
            return False
        with self._lock:
            self.job_start_wall = float(doc.get("job_start_wall",
                                                self.job_start_wall))
            self.generation = int(doc.get("generation", 0)) + 1
            # Fold the released stamp in as the prior lifetimes and
            # zero this ledger's own window (see docstring).
            self.prior_steps = int(doc.get("steps", 0))
            self.steps = 0
            self.prior_step_seconds = float(doc.get("step_seconds", 0.0))
            self.step_seconds = 0.0
            self.prior_timed_steps = int(doc.get("timed_steps", 0))
            self.timed_steps = 0
            self.prior_exposed_seconds = float(
                doc.get("exposed_seconds", 0.0))
            self.exposed_seconds = 0.0
            self.prior_step_exposed_seconds = float(
                doc.get("step_exposed_seconds", 0.0))
            self.step_exposed_seconds = 0.0
            self.prior_stall_seconds = float(doc.get("stall_seconds", 0.0))
            self.stall_seconds = 0.0
            self.prior_step_stall_seconds = float(
                doc.get("step_stall_seconds", 0.0))
            self.step_stall_seconds = 0.0
            self.prior_downtime_seconds = float(
                doc.get("downtime_seconds", 0.0))
            self.downtime_seconds = 0.0
            self.prior_preempt_seconds = float(
                doc.get("preempt_seconds", 0.0))
            self.preempt_seconds = 0.0
            self.prior_replayed_steps = int(doc.get("replayed_steps", 0))
            self.replayed_steps = 0
            self.prior_replay_seconds = float(doc.get("replay_seconds", 0.0))
            self.replay_seconds = 0.0
            # Steps are collective, so the released cursor and this
            # rank's own agree up to the commit racing the drain; the
            # max is right either way.
            self.current_step = max(self.current_step,
                                    int(doc.get("current_step", 0)))
            self.committed_step = max(self.committed_step,
                                      int(doc.get("committed_step", 0)))
            self._source_rank = max(self._source_rank,
                                    int(doc.get("source_rank", 0)))
            self.rank = 0
            self._stamp_owner = True
        self._m_generation.set(self.generation)
        logger.info(
            "goodput: adopted the released ledger stamp (generation %d, "
            "step cursor %d); durable stamping continues in this process",
            self.generation, self.current_step)
        # Claim immediately: the next reader sees an un-released stamp
        # owned by this lifetime.
        self.stamp(force=True)
        return True

    def stamp(self, force: bool = False):
        """Persist the ledger stamp (the ORIGINAL rank 0 only,
        rate-limited by ``HOROVOD_GOODPUT_STAMP_SECONDS``; 0 = every
        commit). Never fsynced — a crash loses at most one stamp
        interval of downtime resolution, and the commit path must stay
        cheap. The KV mirror is handed to a background worker: a down
        rendezvous server (with its connect retries) must never stall
        the training thread."""
        if not self.enabled or self.rank != 0 or not self._stamp_owner:
            return
        now = time.monotonic()
        if not force and now - self._last_stamp_mono < self.stamp_seconds:
            return
        self._last_stamp_mono = now
        doc = self._stamp_doc()
        if self.stamp_path:
            try:
                os.makedirs(os.path.dirname(self.stamp_path) or ".",
                            exist_ok=True)
                atomic_file.atomic_write_text(
                    self.stamp_path, json.dumps(doc), fsync=False)
            except OSError as e:
                logger.warning("goodput stamp write failed: %s", e)
        if self._kv is not None:
            with self._kv_cond:
                self._kv_doc = doc  # latest wins; a backlog is pointless
                if self._kv_thread is None or not self._kv_thread.is_alive():
                    self._kv_thread = threading.Thread(
                        target=self._kv_loop, name="hvd-goodput-kv",
                        daemon=True)
                    self._kv_thread.start()
                self._kv_cond.notify_all()

    def _kv_loop(self):
        while True:
            with self._kv_cond:
                while self._kv_doc is None:
                    self._kv_cond.wait()
                doc = self._kv_doc
                self._kv_doc = None
            try:
                self._kv.put(KV_SCOPE, KV_KEY,
                             json.dumps(doc, separators=(",", ":")).encode())
            except Exception:  # KV down stalls only this worker
                pass

    # -- step demarcation ----------------------------------------------
    def step(self):
        """Explicit step scope: ``with hvd.step(): train_step(...)``."""
        if not self.enabled:
            return _NOOP_SCOPE
        return _StepScope(self)

    def _claim_source(self, source: str) -> bool:
        """Whether boundaries from `source` currently drive the step
        counter (higher-ranked sources take it over permanently). The
        steady state (same source every step) is a lock-free int
        compare — this sits on the per-step hot path."""
        r = _SOURCE_RANK.get(source, 0)
        cur = self._source_rank
        if r == cur:
            return True
        if r < cur:
            return False
        with self._lock:
            if r > self._source_rank:
                self._source_rank = r
                self._boundary_ns = None  # restart the boundary timer
            return r >= self._source_rank

    def _take_exposed_window(self):
        """Drain the since-last-edge windows: (exposed, stall)."""
        with self._lock:
            w = self._window_exposed
            st = self._window_stall
            self._window_exposed = 0.0
            self._window_stall = 0.0
        return w, st

    def auto_step(self, source: str):
        """Automatic step boundary (`wrap_step` call / optimizer
        update / state commit):
        the time since the previous boundary from the SAME winning
        source is one step. The first boundary after a disruption (or
        ever) closes a step whose start was never observed — it still
        COUNTS (the step cursor must track commits 1:1 for replay
        accounting) but carries no duration."""
        if not self.enabled or not self._claim_source(source):
            return
        now_ns = clock.mono_ns()
        with self._lock:
            t0 = self._boundary_ns
            self._boundary_ns = now_ns
        self._finish_step(t0, now_ns, source)

    def _finish_step(self, t0_ns: Optional[int], t1_ns: int, source: str):
        timed = t0_ns is not None
        dur = max(t1_ns - t0_ns, 0) / 1e9 if timed else 0.0
        with self._lock:
            exposed = self._window_exposed
            stall = self._window_stall
            self._window_exposed = 0.0
            self._window_stall = 0.0
            self.steps += 1
            self.current_step += 1
            n = self.current_step
            if timed:
                # In-step exposure: what goodput subtraction uses,
                # clamped to the step's own wall time (cross-thread
                # waits must not over-subtract). Untimed boundary
                # steps contribute 0 step_seconds, so their window —
                # which may hold pre-training waits like the initial
                # broadcast — stays out of the subtraction too.
                self.step_exposed_seconds += min(exposed, dur)
                self.step_stall_seconds += min(stall, dur)
                self.timed_steps += 1
                self.step_seconds += dur
        self._m_steps.inc()
        by_source = self._m_source_steps.get(source)
        if by_source is None:
            by_source = self._m_source_steps[source] = self.registry.counter(
                "horovod_goodput_steps_by_source_total",
                "Demarcated training steps by the boundary source that "
                "drove them",
                labels={"source": source})
        by_source.inc()
        if timed:
            self._m_step_s.observe(dur)
            self._m_exposed_step_s.observe(min(exposed, dur))
            tracer = self.tracer
            if tracer is not None and getattr(tracer, "enabled", False):
                tracer.emit("step", "step", t0_ns, t1_ns - t0_ns,
                            args={"step": n,
                                  "exposed_comm_ms": round(exposed * 1e3,
                                                           3)})

    # -- badput sources ------------------------------------------------
    def note_exposed(self, seconds: float):
        """A collective handle wait actually blocked the caller."""
        if not self.enabled or seconds <= 0:
            return
        self._m_exposed.inc(seconds)
        with self._lock:
            self.exposed_seconds += seconds
            self._window_exposed += seconds

    def note_ckpt_stall(self, seconds: float):
        """Training-thread seconds the durability plane consumed
        (snapshot host copies; the background write itself overlaps)."""
        if not self.enabled or seconds <= 0:
            return
        self._m_stall.inc(seconds)
        with self._lock:
            self.stall_seconds += seconds
            self._window_stall += seconds

    def note_ckpt_skip(self):
        if not self.enabled:
            return
        with self._lock:
            self.stall_skips += 1

    def note_commit(self):
        """A ``state.commit()`` landed: a step boundary (lowest-ranked
        source), the committed-step cursor, and a (rate-limited) stamp."""
        if not self.enabled:
            return
        self.auto_step("commit")
        with self._lock:
            self.committed_step = self.current_step
        self.stamp()

    def note_restore(self, restored_step: Optional[int] = None):
        """The state rolled back (in-memory elastic restore, or a
        durable restore after a kill-all). Steps between the restore
        point and the step cursor were lost and will be re-executed:
        counted ONCE (the cursor rewinds to the restore point, so a
        second restore counts only newly re-run steps) and never
        negative (restoring 'forward' counts nothing).

        `restored_step` is a checkpoint-manifest step, which counts
        elastic COMMITS. It is only comparable to the ledger's cursor
        when commits are the demarcation source; under optimizer or
        explicit demarcation (finer-grained cursors) the manifest
        number would manufacture phantom replay, so the ledger falls
        back to its own committed-step cursor — a sound lower bound in
        its own units."""
        if not self.enabled:
            return
        with self._lock:
            if (restored_step is not None
                    and self._source_rank > _SOURCE_RANK["commit"]):
                restored_step = None
            target = (self.committed_step if restored_step is None
                      else int(restored_step))
            lost = max(self.current_step - target, 0)
            self.current_step = min(self.current_step, target)
            self.committed_step = min(self.committed_step,
                                      self.current_step)
            self.replayed_steps += lost
            mean = self._mean_step_locked()
            replay_s = lost * mean
            self.replay_seconds += replay_s
            # A rollback also invalidates the running boundary/window:
            # the next step starts fresh.
            self._boundary_ns = None
            self._window_exposed = 0.0
            self._window_stall = 0.0
        if lost:
            self._m_replayed.inc(lost)
            self._m_replay_s.inc(replay_s)
            from . import events as events_mod

            events_mod.emit(events_mod.CKPT_REPLAY,
                            severity=events_mod.WARN, rank=self.rank,
                            restored_step=target, lost_steps=lost,
                            replay_seconds=round(replay_s, 3))
            logger.info(
                "goodput: restore to step %d loses %d executed steps "
                "(~%.1fs of replay badput)", target, lost, replay_s)

    def disruption_begin(self, reason: str = "", bucket: str = "failure"):
        """A failure/reset window opened: wall time until
        ``disruption_end`` is restart-badput, and step boundaries are
        suspended so the gap never reads as one giant step. `bucket`
        picks the attribution: ``"failure"`` (the default — crashes,
        liveness evictions, unannounced loss) or ``"preemption"``
        (announced drains; docs/fault_tolerance.md). An already-open
        window keeps its original reason but may be UPGRADED to the
        preemption bucket: the drain notice often arrives after the
        collective failure it caused was already bracketed."""
        if not self.enabled:
            return
        if bucket not in ("failure", "preemption"):
            bucket = "failure"
        with self._lock:
            if self._disrupt_t0 is None:
                self._disrupt_t0 = time.monotonic()
                self._disrupt_reason = reason
                self._disrupt_bucket = bucket
            elif bucket == "preemption":
                self._disrupt_bucket = bucket
            self._boundary_ns = None
        tracer = self.tracer
        if tracer is not None and getattr(tracer, "enabled", False):
            tracer.instant("goodput.disruption", cat="goodput",
                           args={"reason": reason, "bucket": bucket})

    def disruption_end(self):
        """Training is live again; the window closes into the
        restart-downtime bucket (or the preemption bucket for an
        announced drain). No-op without an open window."""
        if not self.enabled:
            return
        with self._lock:
            t0 = self._disrupt_t0
            self._disrupt_t0 = None
            reason = self._disrupt_reason
            self._disrupt_reason = ""
            bucket = self._disrupt_bucket
            self._disrupt_bucket = "failure"
            if t0 is None:
                return
            dt = max(time.monotonic() - t0, 0.0)
            if bucket == "preemption":
                self.preempt_seconds += dt
            else:
                self.downtime_seconds += dt
        if bucket == "preemption":
            self._m_preempt.inc(dt)
        else:
            self._m_downtime.inc(dt)
        logger.info("goodput: %.2fs of %s downtime (%s)", dt, bucket,
                    reason or "disruption")
        self.stamp()

    # -- derived math ---------------------------------------------------
    def _mean_step_locked(self) -> float:
        n = self.prior_timed_steps + self.timed_steps
        s = self.prior_step_seconds + self.step_seconds
        return s / n if n > 0 else 0.0

    def wall_seconds(self, now_wall: Optional[float] = None) -> float:
        now = time.time() if now_wall is None else now_wall
        return max(now - self.job_start_wall, 0.0)

    def _totals(self) -> dict:
        with self._lock:
            return {
                "steps": self.prior_steps + self.steps,
                "step_seconds": self.prior_step_seconds + self.step_seconds,
                "exposed_seconds": (self.prior_exposed_seconds
                                    + self.exposed_seconds),
                "step_exposed_seconds": (self.prior_step_exposed_seconds
                                         + self.step_exposed_seconds),
                "stall_seconds": (self.prior_stall_seconds
                                  + self.stall_seconds),
                "step_stall_seconds": (self.prior_step_stall_seconds
                                       + self.step_stall_seconds),
                "stall_skips": self.stall_skips,
                "downtime_seconds": (self.prior_downtime_seconds
                                     + self.downtime_seconds),
                "preempt_seconds": (self.prior_preempt_seconds
                                    + self.preempt_seconds),
                "replayed_steps": (self.prior_replayed_steps
                                   + self.replayed_steps),
                "replay_seconds": (self.prior_replay_seconds
                                   + self.replay_seconds),
                "current_step": self.current_step,
                "committed_step": self.committed_step,
                "mean_step_seconds": self._mean_step_locked(),
            }

    def goodput_seconds(self, totals: Optional[dict] = None) -> float:
        """Productive compute: step wall time minus the badput that
        happened INSIDE steps — the in-step exposed share (out-of-step
        waits live in other/downtime wall time and subtracting them
        here would double-count), snapshot stalls, and replayed work.
        Clamped at 0 — accounting noise must never go negative."""
        t = totals or self._totals()
        return max(t["step_seconds"] - t["step_exposed_seconds"]
                   - t["step_stall_seconds"] - t["replay_seconds"], 0.0)

    def _ratio_from(self, t: dict, wall: float) -> Optional[float]:
        if t["steps"] <= 0 or wall <= 0:
            return None
        return min(self.goodput_seconds(t) / wall, 1.0)

    def ratio(self, now_wall: Optional[float] = None) -> Optional[float]:
        """goodput_seconds / job wall-clock, in [0, 1]; None before the
        first completed step (no ratio is better than a made-up one)."""
        return self._ratio_from(self._totals(), self.wall_seconds(now_wall))

    def _ratio_or_nan(self) -> float:
        r = self.ratio()
        return float("nan") if r is None else r

    def view(self) -> dict:
        """The full ledger document: the /goodput body's ``local``
        section, the /status ``goodput`` section, and the post-mortem
        embed."""
        t = self._totals()
        wall = self.wall_seconds()
        good = self.goodput_seconds(t)
        badput = {
            "exposed_comm_seconds": round(t["exposed_seconds"], 4),
            "exposed_comm_in_step_seconds": round(
                t["step_exposed_seconds"], 4),
            "ckpt_stall_seconds": round(t["stall_seconds"], 4),
            "ckpt_stall_in_step_seconds": round(
                t["step_stall_seconds"], 4),
            "ckpt_backpressure_skips": t["stall_skips"],
            "restart_downtime_seconds": round(t["downtime_seconds"], 4),
            "preemption_seconds": round(t["preempt_seconds"], 4),
            "replayed_steps": t["replayed_steps"],
            "replay_seconds": round(t["replay_seconds"], 4),
            # Wall time outside steps and outside disruptions: init,
            # input pipeline, evaluation — unattributed overhead.
            "other_seconds": round(
                max(wall - t["step_seconds"] - t["downtime_seconds"]
                    - t["preempt_seconds"], 0.0),
                4),
        }
        out = {
            "enabled": self.enabled,
            "generation": self.generation,
            "job_start_wall": self.job_start_wall,
            "wall_seconds": round(wall, 4),
            "steps": {
                "total": t["steps"],
                "this_process": self.steps,
                "current_step": t["current_step"],
                "committed_step": t["committed_step"],
                "mean_step_seconds": round(t["mean_step_seconds"], 6),
            },
            "goodput": {
                "seconds": round(good, 4),
                # From the same totals/wall as the other fields, so the
                # document is internally consistent under concurrency.
                "ratio": self._ratio_from(t, wall),
            },
            "badput": badput,
        }
        if self.step_flops > 0 and t["mean_step_seconds"] > 0:
            flops_s = self.step_flops / t["mean_step_seconds"]
            out["flops"] = {
                "step_flops": self.step_flops,
                "achieved_flops_per_second": flops_s,
            }
            if self.peak_flops > 0:
                out["flops"]["mfu"] = round(flops_s / self.peak_flops, 4)
        return out

    def status_summary(self) -> dict:
        """Compact form for the /status ``goodput`` section."""
        v = self.view()
        return {
            "enabled": v["enabled"],
            "generation": v["generation"],
            "steps": v["steps"]["total"],
            "goodput_ratio": v["goodput"]["ratio"],
            "exposed_comm_seconds": v["badput"]["exposed_comm_seconds"],
            "restart_downtime_seconds":
                v["badput"]["restart_downtime_seconds"],
            "preemption_seconds": v["badput"]["preemption_seconds"],
            "replayed_steps": v["badput"]["replayed_steps"],
        }


# ---------------------------------------------------------------------------
# Process-wide ledger (survives elastic engine swaps). The in-process
# multi-rank test harness constructs private ledgers instead.

_current: Optional[GoodputLedger] = None
_current_lock = threading.Lock()


def _default_stamp_path() -> Optional[str]:
    d = env_cfg.goodput_dir()
    return os.path.join(d, STAMP_NAME) if d else None


def _kv_from_env():
    addr = env_cfg.get_str(env_cfg.RENDEZVOUS_ADDR)
    port = env_cfg.get_int(env_cfg.RENDEZVOUS_PORT, 0)
    if addr and port:
        from ..backend.rendezvous import RendezvousClient

        return RendezvousClient(addr, port)
    return None


def current(rank: Optional[int] = None) -> GoodputLedger:
    """The process ledger, created on first use. `rank` seeds the
    first creation when the caller knows better than the environment —
    mesh mode has no HOROVOD_RANK (that absence is what selects mesh
    mode), so basics.init passes jax's process index; every process
    defaulting to rank 0 there would make N stamp owners."""
    global _current
    with _current_lock:
        if _current is None:
            if rank is None:
                rank = env_cfg.get_int(env_cfg.RANK, 0)
            _current = GoodputLedger(
                rank=rank,
                stamp_path=_default_stamp_path(),
                kv=_kv_from_env() if rank == 0 else None)
        return _current


def set_current(led: Optional[GoodputLedger]):
    global _current
    with _current_lock:
        _current = led


def active() -> Optional[GoodputLedger]:
    """The process ledger if one exists — the hook form used by the
    checkpoint/elastic planes, which must stay no-ops in processes that
    never initialized goodput accounting."""
    return _current


def for_engine(registry, rank: int, tracer=None) -> GoodputLedger:
    """The ledger an Engine should feed. Engines on the process-default
    registry share the process ledger (it outlives them across elastic
    resets); an engine with a private registry (the in-process
    multi-rank harness) gets a private ledger so per-"rank" accounting
    stays separable."""
    from . import telemetry

    if registry is telemetry.default_registry():
        led = current()
        if rank == 0 and led.rank != 0 and not led._stamp_owner:
            # A survivor promoted to coordinator by elastic
            # renumbering. If the previous owner RELEASED the stamp (a
            # graceful drain), adopt it — ownership hands off and
            # durable accounting continues here. Otherwise it never
            # loaded the job-lifetime stamp, so it must not overwrite
            # it with fresh-lifetime numbers — durable stamping stays
            # with the original rank 0's lifetime (per-lifetime
            # accounting continues locally).
            if not led.try_adopt_stamp():
                logger.info(
                    "goodput: promoted to rank 0 mid-job; durable ledger "
                    "stamping remains disabled in this process")
        led.rank = rank  # elastic renumbering: the live rank wins
    else:
        led = GoodputLedger(registry=registry, rank=rank)
    if tracer is not None:
        led.tracer = tracer
    return led


# -- module-level hook forms (no-ops without a live ledger) -----------------

def step():
    """``hvd.step()``: demarcate one training step explicitly."""
    return current().step()


def auto_step(source: str):
    led = active()
    if led is not None:
        led.auto_step(source)


def note_commit():
    led = active()
    if led is not None:
        led.note_commit()


def note_restore(restored_step: Optional[int] = None):
    led = active()
    if led is not None:
        led.note_restore(restored_step)


def note_ckpt_stall(seconds: float):
    led = active()
    if led is not None:
        led.note_ckpt_stall(seconds)


def note_ckpt_skip():
    led = active()
    if led is not None:
        led.note_ckpt_skip()


def disruption_begin(reason: str = "", bucket: str = "failure"):
    led = active()
    if led is not None:
        led.disruption_begin(reason, bucket=bucket)


def release_stamp():
    """Graceful-drain hook: the draining owner's final ``draining``
    stamp (no-op without a live owning ledger)."""
    led = active()
    if led is not None:
        led.release_stamp()


def disruption_end():
    led = active()
    if led is not None:
        led.disruption_end()
