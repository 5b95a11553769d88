"""Distributed tracing plane: cross-rank collective spans + an
always-on flight recorder (docs/tracing.md).

The reference ships a per-rank Chrome-trace timeline
(ref: horovod/common/timeline.{h,cc}) and PR 2's telemetry answers
"how much / how often" — neither answers "where did this step's 40 ms
go, and which rank made everyone wait". This module is the missing
layer, three pieces:

* **Span API + flight recorder** — `Tracer.span()` records
  (trace_id, name, category, t0, duration, thread) tuples into a
  fixed-size in-memory ring (`SpanRecorder`): append-only,
  monotonic-ns stamps from the shared `utils.clock` anchor, always on,
  never any I/O on the hot path. The ring overwrites its oldest events
  (counted in ``horovod_trace_events_dropped_total{source="recorder"}``)
  so the last ``HOROVOD_TRACE_BUFFER_EVENTS`` events are always
  available — a black-box flight recorder, dumped on failure.

* **Cross-rank correlation** — the coordinator assigns a trace id per
  `Response`, carried on the wire (common/message.py, the same
  trailing-field pattern as the executor channel id), so every rank's
  spans for one collective share an id. Cache-replayed responses get
  ids from a deterministic per-rank replay sequence (odd id space —
  the fast path exchanges no per-response bytes, but every rank emits
  the same cached responses in the same order, so local counters
  agree). The active id is a thread-local scope (`trace_scope`) the
  engine sets around each response; backend spans inherit it
  implicitly, including across the hop onto a persistent TCP sender
  thread (captured at enqueue).

* **Collection + rendering** — each rank piggybacks new-event batches
  on the telemetry push it already gathers to rank 0
  (engine/controller.py); rank 0's `TraceCollector` accumulates them
  (dedup by per-rank sequence number), aligns clocks with per-peer
  offsets estimated from heartbeat send/ack RTTs (`estimate_offset`,
  fed by common/health.py; wall-clock anchors as the fallback), and
  `render_chrome` merges everything into one Perfetto/Chrome document
  with one process lane per rank — served at `/trace`, dumped to
  ``HOROVOD_TRACE_FILE``, and stitched into failure post-mortems
  under ``HOROVOD_TRACE_DIR``.

Event tuple layout (also the wire/JSON batch format):

    (seq, trace_id, name, cat, t0_ns, dur_ns, thread, args|None)

``seq`` is a per-rank monotonically increasing index (the dedup key);
``t0_ns`` is a raw ``monotonic_ns`` stamp — rendering subtracts the
per-rank clock offset and the coordinator's anchor.
"""
from __future__ import annotations

import contextlib
import glob
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..utils import atomic_file, clock
from ..utils import env as env_cfg
from ..utils.logging import get_logger

logger = get_logger()

# Span categories (the critical-path analyzer attributes time by these).
CAT_NEGOTIATE = "negotiate"
CAT_QUEUE = "queue"
CAT_EXEC = "exec"
CAT_XFER = "xfer"
CAT_COMPUTE = "compute"
# Step-demarcation spans from the goodput ledger (docs/goodput.md):
# one X event per training step, args carry the step number and its
# exposed-comm share — what critical_path.py groups collectives under.
CAT_STEP = "step"


# ---------------------------------------------------------------------------
# Under jit: regions and step spans in the XLA profile (docs/tracing.md,
# "Under jit"). The ring below cannot see a compiled step; these names
# go where `jax.profiler` looks. Scopes are `jax.named_scope`s: they
# prefix the name stack XLA keeps as metadata of every op issued inside
# them, forward and backward, and change nothing else of the program.
# Spans are `annotate`d on the host and land in the profiler's file
# beside the device lanes, on its clock; with no profile being taken an
# annotation is a flag test. The benchmark's reader
# (benchmark/trace_regions.py) keeps its own copy of this vocabulary; a
# test compares the two.

#: log-softmax, one-hot product and mean of `parallel.train.softmax_xent`.
SCOPE_LOSS = "hvd.loss"
#: The optimizer pass: `make_train_step`'s `tx.update` + `apply_updates`,
#: `DistributedOptimizer`'s inner `optimizer.update`.
SCOPE_OPTIMIZER = "hvd.optimizer"
#: A latent attention's own work around the attention kernel: the five
#: projections, the two inner norms, the rotary positions
#: (`models/latent_moe.py::LatentAttention`); not the kernel.
SCOPE_ATTN_LATENT = "hvd.attn.latent"
#: An attention's own work around the attention call: the projections
#: (`models/transformer.py::MultiHeadAttention`'s `qkv` / `out`;
#: `models/window_moe.py::GatedAttention`'s q / k / v / gate / output,
#: the rotary positions and the gate's product); not the call.
SCOPE_ATTN_PROJ = "hvd.attn.proj"
#: A delta-rule linear attention's own work between its projections
#: (`models/linear_moe.py::DeltaAttention`): the short convolutions,
#: the query / key norms, the decay and write-strength gates, the
#: chunked recurrence's kernels (`ops/kda.py`), the per-head output
#: norm and its gate; forward and backward. The projections are
#: `SCOPE_ATTN_PROJ`'s.
SCOPE_ATTN_KDA = "hvd.attn.kda"
#: The attention call of a sliding-window layer, forward and backward
#: (the kernels and the layout copies around them).
SCOPE_ATTN_WINDOW = "hvd.attn.window"
#: The attention call of a full (global) layer: `GatedAttention`'s
#: without a window, `MultiHeadAttention`'s (flash kernels and their
#: layout copies, or XLA's dense attention).
SCOPE_ATTN_FULL = "hvd.attn.full"
#: A block's dense feed-forward, entered at its call site: its products,
#: activation and dropout, forward and backward. Not a routed layer's
#: shared expert, which is `SCOPE_MOE_EXPERTS`'.
SCOPE_MLP = "hvd.mlp"
#: A norm entered at its call site: a block's pre-attention and pre-FFN
#: norms, the final norm, the prediction module's; and what XLA fuses
#: under its root. Not a latent attention's inner norms.
SCOPE_NORM = "hvd.norm"
#: The token (and position) embedding: the gather forward, the scatter
#: into the table backward.
SCOPE_EMBED = "hvd.embed"
#: A routed layer's routing: router, top-k, gates, the sorts, the
#: kernels that move rows into the dispatch buffer and sum them back
#: (`models/latent_moe.py::RoutedExperts`, `ops/routed_rows.py`).
SCOPE_MOE_ROUTE = "hvd.moe.route"
#: A routed layer's expert work: the grouped products over the experts
#: held here, the activation between them, and the shared expert.
SCOPE_MOE_EXPERTS = "hvd.moe.experts"
#: The multi-token-prediction module, outermost: its norms, projection
#: and block, and the shared head applied to it.
SCOPE_MTP = "hvd.mtp"
#: One call of a step function the library built (`make_train_step`'s
#: step, `wrap_step`'s wrapper); arg `step` = number of this call of
#: this function in the process.
SPAN_STEP = "hvd.step"
#: In `wrap_step`: flatten the arguments, make the cache key, look up.
SPAN_WRAP_PREPARE = "hvd.wrap_step.prepare"
#: In `wrap_step`, on a cache miss: build the `shard_map` / `jax.jit`.
SPAN_WRAP_BUILD = "hvd.wrap_step.build"
#: In `wrap_step`: the call of the built function, jit's dispatch.
SPAN_WRAP_CALL = "hvd.wrap_step.call"


def annotate(name: str, **args):
    """A host span of the XLA profile: a context manager that records
    `name` with `args` on the calling thread while `jax.profiler` is
    tracing, and costs a flag test while it is not. A span's parent is
    the span that encloses it on the same thread."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


# ---------------------------------------------------------------------------
# Thread-local trace-id scope (the engine sets it around each response;
# same shape as backend/base.py's channel scope).

_trace_ctx = threading.local()


def current_trace() -> int:
    """Trace id spans on the calling thread inherit; 0 outside any
    scope (control plane, heartbeats, direct backend use)."""
    return getattr(_trace_ctx, "trace_id", 0)


@contextlib.contextmanager
def trace_scope(trace_id: int):
    prev = getattr(_trace_ctx, "trace_id", None)
    _trace_ctx.trace_id = trace_id
    try:
        yield
    finally:
        if prev is None:
            del _trace_ctx.trace_id
        else:
            _trace_ctx.trace_id = prev


# ---------------------------------------------------------------------------
# Flight recorder

# Per-thread cached thread name: threading.current_thread().name costs
# ~550ns; the thread-local getattr ~100ns. Names never change for the
# engine's long-lived workers.
_tname_cache = threading.local()


def _thread_name() -> str:
    n = getattr(_tname_cache, "v", None)
    if n is None:
        n = _tname_cache.v = threading.current_thread().name
    return n


class SpanRecorder:
    """Fixed-size ring of trace events: append-only, no I/O, overwrite
    on wrap. The per-rank sequence number never resets, so consumers
    read incrementally with `batch_since` and overwrites are exactly
    `total - retained` (the drop accounting).

    Hot-path design: `append` is a plain `list.append` (GIL-atomic, no
    lock) with the seq drawn from an `itertools.count` (also atomic);
    the ring is enforced by an amortized trim once the list doubles
    past capacity — ~1 lock acquisition per `capacity` appends instead
    of one per event, which is what keeps the always-on recorder under
    the <2% overhead budget on a saturated box. Between trims the
    recorder briefly retains MORE than `capacity` events (never
    fewer); `snapshot` presents exactly the last `capacity`."""

    __slots__ = ("capacity", "_buf", "_seq", "_trim_at", "_lock",
                 "_m_dropped")

    def __init__(self, capacity: int, registry=None):
        self.capacity = max(int(capacity), 0)
        self._buf: List[tuple] = []
        self._seq = itertools.count()
        self._trim_at = 2 * self.capacity
        self._lock = threading.Lock()
        self._m_dropped = None
        if self.capacity and registry is not None:
            self._m_dropped = registry.counter(
                "horovod_trace_events_dropped_total",
                "Trace events lost before reaching an output (flight-"
                "recorder ring overwrites, timeline writer-queue drops)",
                labels={"source": "recorder"})

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    def append(self, trace_id: int, name: str, cat: str, t0_ns: int,
               dur_ns: int, thread: str, args: Optional[dict] = None):
        if not self.capacity:
            return
        buf = self._buf
        buf.append((next(self._seq), trace_id, name, cat, t0_ns, dur_ns,
                    thread, args))
        if len(buf) >= self._trim_at:
            self._trim()

    def _trim(self):
        # Amortized ring enforcement. Overwriting events that were
        # never dumped IS a drop: without the counter a truncated
        # post-mortem would read as the whole story. (The counter
        # advances at trim time; the `dropped` property is exact.)
        with self._lock:
            excess = len(self._buf) - self.capacity
            if excess > 0:
                del self._buf[:excess]
                if self._m_dropped is not None:
                    self._m_dropped.inc(excess)

    def _total(self) -> int:
        buf = self._buf
        return buf[-1][0] + 1 if buf else 0

    def depth(self) -> int:
        return min(len(self._buf), self.capacity)

    @property
    def dropped(self) -> int:
        """Events no longer retained by the ring (exact)."""
        return max(self._total() - self.depth(), 0)

    def snapshot(self) -> List[tuple]:
        """The last `capacity` retained events, oldest first."""
        with self._lock:
            evs = list(self._buf)
        # Concurrent appenders may interleave adjacent seqs; order by
        # seq so consumers (and the ring cut) see the true sequence.
        evs.sort(key=lambda e: e[0])
        return evs[-self.capacity:]

    def batch_since(self, cursor: int, limit: int = 4096
                    ) -> Tuple[List[tuple], int]:
        """Events with seq >= cursor (the OLDEST `limit` of them) and
        the next cursor. Oldest-first with the cursor advancing only
        past what was returned, so a backlog bigger than one batch
        drains across successive pushes instead of being silently
        skipped; events the ring overwrote before collection show as a
        cursor gap and are already counted by the trim drop counter.
        Non-destructive: the ring keeps its last-N for post-mortems
        regardless of collection."""
        evs = [e for e in self.snapshot() if e[0] >= cursor]
        if len(evs) > limit:
            evs = evs[:limit]
        nxt = evs[-1][0] + 1 if evs else self._total()
        return evs, nxt


# ---------------------------------------------------------------------------
# Span API

class _Span:
    """Context manager recording one complete event on exit (the E side
    fires even when the body raises, so a failed op still leaves its
    span in the flight recorder — that IS the post-mortem story).

    The exit path is deliberately inlined — no helper calls — because
    span cost on the data-plane hot loops is dominated by Python call
    overhead, and the always-on recorder carries a <2% overhead budget
    (docs/tracing.md)."""

    __slots__ = ("_rec", "_name", "_cat", "_trace", "_args", "_t0")

    def __init__(self, rec: SpanRecorder, name: str, cat: str,
                 trace_id: Optional[int], args: Optional[dict]):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._trace = trace_id
        self._args = args

    def __enter__(self):
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        tid = self._trace
        if tid is None:
            tid = getattr(_trace_ctx, "trace_id", 0)
        tn = getattr(_tname_cache, "v", None)
        if tn is None:
            tn = _tname_cache.v = threading.current_thread().name
        rec = self._rec
        buf = rec._buf
        buf.append((next(rec._seq), tid, self._name, self._cat, self._t0,
                    t1 - self._t0, tn, self._args))
        if len(buf) >= rec._trim_at:
            rec._trim()
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Span recorder + dump helpers for one engine (injectable per
    engine like the telemetry registries; real one-process-per-rank
    jobs construct it on the process default registry)."""

    def __init__(self, registry=None, capacity: Optional[int] = None):
        if capacity is None:
            capacity = env_cfg.trace_buffer_events()
        if capacity > 0 and registry is None:
            from . import telemetry

            registry = telemetry.default_registry()
        self.recorder = SpanRecorder(capacity, registry)
        self.enabled = capacity > 0
        self.last_dump: Optional[str] = None

    def span(self, name: str, cat: str = CAT_EXEC,
             trace_id: Optional[int] = None, args: Optional[dict] = None):
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self.recorder, name, cat, trace_id, args)

    def emit(self, name: str, cat: str, t0_ns: int, dur_ns: int,
             trace_id: Optional[int] = None, args: Optional[dict] = None):
        """Record a span with explicit timestamps (queue dwell, sender
        dwell — measured across threads, not with a context manager).
        Inlined like _Span.__exit__ — same hot-path budget."""
        if not self.enabled:
            return
        if trace_id is None:
            trace_id = getattr(_trace_ctx, "trace_id", 0)
        tn = getattr(_tname_cache, "v", None)
        if tn is None:
            tn = _tname_cache.v = threading.current_thread().name
        rec = self.recorder
        buf = rec._buf
        buf.append((next(rec._seq), trace_id, name, cat, t0_ns,
                    max(dur_ns, 0), tn, args))
        if len(buf) >= rec._trim_at:
            rec._trim()

    def instant(self, name: str, cat: str = "mark",
                trace_id: Optional[int] = None,
                args: Optional[dict] = None):
        self.emit(name, cat, clock.mono_ns(), 0, trace_id, args)

    def status(self) -> dict:
        """Recorder state for the /status `trace` view."""
        return {
            "enabled": self.enabled,
            "buffer_events": self.recorder.capacity,
            "depth": self.recorder.depth(),
            "dropped": self.recorder.dropped,
            "last_dump": self.last_dump,
        }

    # -- failure post-mortems ------------------------------------------
    def dump_flight(self, path: str, rank: int,
                    extra: Optional[dict] = None) -> str:
        """Write this rank's full flight-recorder contents (plus the
        process clock anchor, so offline stitching can align it) as one
        JSON document. The black-box dump on engine death."""
        doc = {
            "rank": rank,
            "anchor": clock.anchor_meta(),
            "dropped": self.recorder.dropped,
            "events": self.recorder.snapshot(),
        }
        if extra:
            doc.update(extra)
        atomic_file.atomic_write(path, lambda f: json.dump(doc, f),
                                 mode="w")
        self.last_dump = path
        return path


# Shared inert tracer: the default for backends constructed outside an
# engine (tests, direct use). Never touches a registry.
NULL_TRACER = Tracer(capacity=0)


# ---------------------------------------------------------------------------
# Clock alignment

def estimate_offset(remote_sent_ns: int, echo_ns: int, echo_recv_ns: int,
                    local_recv_ns: int) -> Tuple[int, int]:
    """NTP-style offset estimate from one heartbeat exchange. The
    incoming frame carries the peer's send stamp (`remote_sent_ns`, its
    clock), an echo of OUR last stamp it saw (`echo_ns`, our clock) and
    its local receipt time of that stamp (`echo_recv_ns`, its clock);
    we observe arrival at `local_recv_ns` (our clock).

        rtt    = (local_recv - echo) - (remote_sent - echo_recv)
        offset = remote_sent - (local_recv - rtt/2)

    Returns (offset_ns, rtt_ns): offset is the peer clock MINUS ours —
    subtract it from a peer timestamp to land on our timebase. Estimates
    from low-RTT exchanges bound the error by rtt/2 (the classic NTP
    argument), which is why the health monitor keeps the minimum-RTT
    sample."""
    rtt = (local_recv_ns - echo_ns) - (remote_sent_ns - echo_recv_ns)
    if rtt < 0:
        rtt = 0
    offset = remote_sent_ns - (local_recv_ns - rtt // 2)
    return offset, rtt


def wall_anchor_offset(remote_anchor: Optional[dict],
                       local_anchor: Optional[dict]) -> int:
    """Fallback peer-clock offset from the wall-clock identity each
    process stamps into its trace blobs (utils/clock.anchor_meta):
    assume the wall clocks agree (same box, or NTP-disciplined hosts)
    and solve for the monotonic-clock offset. Exact for in-process
    multi-rank tests (same anchors → 0)."""
    try:
        return int(
            (remote_anchor["mono_anchor_ns"] - remote_anchor["wall_anchor_ns"])
            - (local_anchor["mono_anchor_ns"] - local_anchor["wall_anchor_ns"])
        )
    except (KeyError, TypeError):
        return 0


# ---------------------------------------------------------------------------
# Rank-0 collection

class TraceCollector:
    """Per-rank event batches accumulated on the coordinator (bounded
    to the flight-recorder capacity per rank), deduplicated by the
    per-rank sequence number so overlapping batches are harmless."""

    def __init__(self, size: int, capacity: Optional[int] = None):
        if capacity is None:
            capacity = env_cfg.trace_buffer_events()
        self.size = size
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._events: Dict[int, deque] = {}
        self._anchors: Dict[int, dict] = {}
        self._last_seq: Dict[int, int] = {}

    def ingest(self, rank: int, events: List, anchor: Optional[dict] = None):
        with self._lock:
            dq = self._events.get(rank)
            if dq is None:
                dq = self._events[rank] = deque(maxlen=self.capacity)
            last = self._last_seq.get(rank, -1)
            for e in events:
                try:
                    seq = int(e[0])
                except (TypeError, ValueError, IndexError):
                    continue
                if seq <= last:
                    continue
                dq.append(tuple(e))
                last = seq
            self._last_seq[rank] = last
            if anchor:
                self._anchors[rank] = dict(anchor)

    def ingest_blob(self, rank: int, blob: bytes):
        """Extract the span batch a rank piggybacked on its telemetry
        push; tolerant of blobs without one (older ranks, tracing
        off)."""
        try:
            d = json.loads(blob.decode("utf-8"))
            spans = d.get("spans")
            anchor = d.get("anchor")
        except Exception:
            return  # a malformed blob must never take down the cycle loop
        if spans:
            self.ingest(rank, spans, anchor)

    def segments(self, offsets: Optional[Dict[int, int]] = None,
                 local_anchor: Optional[dict] = None) -> List[dict]:
        """Per-rank segments for `render_chrome`. Offsets: the health
        plane's RTT-estimated peer offsets when available, wall-anchor
        alignment otherwise."""
        offsets = offsets or {}
        out = []
        with self._lock:
            ranks = sorted(self._events)
            for r in ranks:
                off = offsets.get(r)
                anchor = self._anchors.get(r)
                if off is None:
                    off = wall_anchor_offset(anchor, local_anchor) \
                        if anchor and local_anchor else 0
                out.append({
                    "rank": r,
                    "events": list(self._events[r]),
                    "anchor": anchor,
                    "offset_ns": int(off),
                })
        return out

    def status(self) -> Dict[str, int]:
        with self._lock:
            return {str(r): len(dq) for r, dq in sorted(self._events.items())}


# ---------------------------------------------------------------------------
# Chrome/Perfetto rendering

def chrome_events(segments: List[dict], base_ns: int) -> List[dict]:
    """Merge per-rank event segments into one Chrome-trace event list:
    pid = rank (one process lane per rank), tid = thread within the
    rank, ts = microseconds on the coordinator's timebase (each event's
    raw monotonic stamp minus the segment's peer-clock offset minus
    `base_ns`). Every X event carries its trace id in args, which is
    what the Perfetto query (and scripts/critical_path.py) correlates
    across lanes."""
    out: List[dict] = []
    for seg in segments:
        pid = int(seg["rank"])
        host = (seg.get("anchor") or {}).get("host") or seg.get("host")
        pname = f"rank {pid}" + (f" ({host})" if host else "")
        out.append({"ph": "M", "name": "process_name", "pid": pid,
                    "args": {"name": pname}})
        out.append({"ph": "M", "name": "process_sort_index", "pid": pid,
                    "args": {"sort_index": pid}})
        offset = int(seg.get("offset_ns", 0))
        tids: Dict[str, int] = {}
        for ev in seg["events"]:
            try:
                _, trace_id, name, cat, t0, dur, thread, args = ev
            except (TypeError, ValueError):
                continue
            tid = tids.get(thread)
            if tid is None:
                tid = tids[thread] = len(tids) + 1
                out.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": str(thread)}})
            ev_args = {"trace_id": trace_id}
            if args:
                ev_args.update(args)
            out.append({
                "ph": "X",
                "name": str(name),
                "cat": str(cat),
                "pid": pid,
                "tid": tid,
                "ts": (int(t0) - offset - base_ns) / 1e3,
                "dur": int(dur) / 1e3,
                "args": ev_args,
            })
    return out


def render_chrome(segments: List[dict], base_ns: Optional[int] = None,
                  metadata: Optional[dict] = None) -> dict:
    """Full Chrome-trace document (object form: Perfetto ignores extra
    top-level keys, so the clock anchor and any post-mortem verdict
    ride along)."""
    if base_ns is None:
        base_ns = clock.MONO_ANCHOR_NS
    doc = {
        "traceEvents": chrome_events(segments, base_ns),
        "displayTimeUnit": "ms",
        "horovod_clock": clock.anchor_meta(),
    }
    if metadata:
        doc.update(metadata)
    return doc


# ---------------------------------------------------------------------------
# Failure post-mortems

FLIGHT_PREFIX = "flight_rank"
POSTMORTEM_NAME = "postmortem.json"


def flight_path(trace_dir: str, rank: int) -> str:
    return os.path.join(trace_dir, f"{FLIGHT_PREFIX}{rank}.json")


def stitch_post_mortem(trace_dir: str, verdict: str = "",
                       health: Optional[dict] = None,
                       expect_ranks: Optional[int] = None,
                       grace_s: float = 5.0,
                       out_name: str = POSTMORTEM_NAME,
                       offsets: Optional[Dict[int, int]] = None
                       ) -> Optional[str]:
    """Coordinator-side black box: read every rank's flight dump under
    `trace_dir` (polling up to `grace_s` for stragglers still writing —
    the dumps race the stitch on an engine death), align clocks via
    the health plane's RTT-estimated `offsets` (wall anchors as the
    fallback — those trust each host's wall clock verbatim), and write
    one merged Chrome trace carrying the health verdict. Each rank's
    summary records the alignment actually applied as ``skew_ns``, so
    incident tooling (scripts/incident_report.py) can re-order
    cross-host events on one timebase. Lifecycle events riding the
    flight dumps land as instant markers in the merged trace. Returns
    the output path, or None if no dumps appeared."""
    deadline = time.monotonic() + max(grace_s, 0.0)
    paths: List[str] = []
    while True:
        paths = sorted(glob.glob(
            os.path.join(trace_dir, f"{FLIGHT_PREFIX}*.json")))
        if expect_ranks is not None and len(paths) >= expect_ranks:
            break
        if time.monotonic() >= deadline:
            break
        time.sleep(0.05)
    if not paths:
        return None
    segments = []
    local_anchor = None
    docs = []
    for p in paths:
        try:
            with open(p) as f:
                docs.append(json.load(f))
        except (OSError, ValueError):
            continue
    for d in docs:
        if d.get("rank") == 0:
            local_anchor = d.get("anchor")
    if local_anchor is None and docs:
        local_anchor = docs[0].get("anchor")
    offsets = offsets or {}
    for d in docs:
        anchor = d.get("anchor")
        r = int(d.get("rank", -1))
        off = offsets.get(r)
        if off is None:
            off = wall_anchor_offset(anchor, local_anchor)
        segments.append({
            "rank": r,
            "events": d.get("events", []),
            "anchor": anchor,
            "offset_ns": int(off),
        })
    skew_by_rank = {s["rank"]: s["offset_ns"] for s in segments}
    base = (local_anchor or {}).get("mono_anchor_ns", 0)
    doc = render_chrome(segments, base_ns=base, metadata={
        "horovod_postmortem": {
            "verdict": verdict,
            "health": health,
            "ranks": sorted(s["rank"] for s in segments),
            "per_rank": {
                str(d.get("rank")): {
                    "reason": d.get("reason", ""),
                    "events": len(d.get("events", [])),
                    "dropped": d.get("dropped", 0),
                    # Health plane (docs/health.md): the flight dump
                    # carries the rank's scalar time-series and latched
                    # alerts; the summary counts them so a reader knows
                    # which flight file holds history worth opening.
                    "timeseries_samples": len(
                        (d.get("timeseries") or {}).get("samples", [])),
                    "alerts_firing": (d.get("alerts") or {}).get(
                        "firing", []),
                    # Goodput ledger (docs/goodput.md): how much of the
                    # job had become training when it died — the badput
                    # breakdown rides the flight dump itself.
                    "goodput_ratio": ((d.get("goodput") or {})
                                      .get("goodput") or {}).get("ratio"),
                    "goodput_steps": ((d.get("goodput") or {})
                                      .get("steps") or {}).get("total"),
                    # Clock alignment actually applied to this rank's
                    # lane (peer mono clock minus the coordinator's,
                    # ns): RTT-estimated when the health plane had a
                    # sample, wall-anchor fallback otherwise.
                    "skew_ns": skew_by_rank.get(int(d.get("rank", -1)), 0),
                    "lifecycle_events": len(d.get("lifecycle") or []),
                } for d in docs
            },
        },
    })
    # Lifecycle markers (docs/events.md): each dump's events-plane tail
    # becomes instant events on that rank's lane, so a re-mesh / drain /
    # swap reads inline with the spans around it.
    from ..utils import chrome_trace

    for d in docs:
        r = int(d.get("rank", -1))
        off = skew_by_rank.get(r, 0)
        for led in d.get("lifecycle") or []:
            try:
                ts_us = (int(led["mono_ns"]) - off - base) / 1e3
            except (KeyError, TypeError, ValueError):
                continue
            doc["traceEvents"].append(chrome_trace.instant(
                str(led.get("kind", "event")), ts_us, pid=r,
                cat="lifecycle",
                args={k: v for k, v in led.items() if k != "mono_ns"}))
    out = os.path.join(trace_dir, out_name)
    atomic_file.atomic_write(out, lambda f: json.dump(doc, f), mode="w")
    return out
