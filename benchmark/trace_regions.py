"""From a profiler trace to the regions of a step and the program's own
host spans: what the names the program gives itself say about where a
step's device time and a step call's host time go.

`trace_reduce.py` reads a trace through `jax.profiler.ProfileData`, which
shows an op's HLO instruction, start and duration. The `.xplane.pb`
holds more (seen by hand in the recorded traces under
tests/benchmarking/data/, jax 0.9.0 / libtpu 0.0.34): every
`XEventMetadata` of a `/device:TPU:<n>` plane carries the stats `tf_op`
(the op's jax name stack,
`jit(train_step)/transpose(jvp(TransformerLM))/stack/layer_0/mlp/wi/dot_general:`),
`hlo_category`, `flops`, `bytes_accessed` and `source`, and a host
event carries the arguments of its `TraceAnnotation` as stats.
`ProfileData` exposes neither the metadata's stats nor a line's id, so
this file walks the protobuf's wire format itself (xplane.proto is seven
small messages; no TensorFlow, no generated code).

The name stack is jax's and flax's: `jvp(<Model>)/...` is the forward
pass, `transpose(jvp(<Model>))/...` the backward pass, the path below it
the flax modules. What no module issues, the program names
(horovod_tpu/common/tracing.py, docs/tracing.md "Under jit"): the scopes
`hvd.loss` and `hvd.optimizer`, and on the host the spans `hvd.step`
and `hvd.wrap_step.{prepare,build,call}`. The copy of that vocabulary
below is the benchmark's own: nothing here imports `horovod_tpu`, and
tests/benchmarking/test_benchmark_trace_regions.py compares the two.

Like `trace_reduce.py`: arithmetic on plain tuples in seconds, tested on
a synthetic list with known answers; a loader tested on traces recorded
on the chip (tests/benchmarking/test_benchmark_trace_regions.py);
`python -m benchmark.trace_regions <file> [log_every]` prints the
partition, the host spans and the idle gaps of the same window of whole
steps that `trace_reduce.reduce` takes.

A fused op counts where XLA's metadata puts it: a fusion is one event
with one `tf_op`. On the chip (PERF.md section 6, PR 25) a matmul fusion
keeps the matmul's name whatever is fused behind it, so the AdamW update
that XLA fuses into each weight-gradient matmul is `backward` (or
`loss_head`) time, and `optimizer` holds only the updates that run in
fusions of their own; in a user's step the update fused under the root
of their own unnamed `optax.apply_updates` is `unscoped`.

How a later PR adds a metric that reads a region or a span. A reader
under `layer_metrics/` reaches this file's tables as `ctx.regions`
(`harness.Context` loads them on first use from the traced run's file,
which stays until the readers have run): each name of `METRICS` is a
module there (`return ctx.regions.metrics()[<name>]`) and an entry of
`per_layer`. A new region is a name in `REGIONS` and a rule in
`region_of`, a new span a name in the program's vocabulary and here:
both are edits to this file, a `benchmark` PR's business.
`run.py --trace 1 --trace-dir <dir>` keeps the file for this module's
command.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import statistics
import sys
from typing import Iterable, NamedTuple, Optional, Sequence

from benchmark import trace_reduce
from benchmark.trace_reduce import Event

# The program's vocabulary (horovod_tpu/common/tracing.py), copied.
SCOPE_LOSS = "hvd.loss"
SCOPE_OPTIMIZER = "hvd.optimizer"
SPAN_STEP = "hvd.step"
SPAN_WRAP_PREPARE = "hvd.wrap_step.prepare"
SPAN_WRAP_BUILD = "hvd.wrap_step.build"
SPAN_WRAP_CALL = "hvd.wrap_step.call"
PROGRAM_PREFIX = "hvd."

# The benchmark loop's own spans (harness.SPANS), for the comparison of
# `hvd.step` with the `dispatch` that encloses it.
LOOP_SPANS = ("feed", "dispatch", "log_fetch")

# flax module names of the models' output heads (models/transformer.py).
HEAD_MODULES = ("lm_head", "mlm_head")

REGIONS = ("forward", "backward", "loss_head", "optimizer", "unscoped")

# The per-layer metrics this file can feed, all in ms: a region's device
# time a step, or the median duration of a host span.
METRICS = {
    "forward_ms_per_step": "forward",
    "backward_ms_per_step": "backward",
    "loss_head_ms_per_step": "loss_head",
    "optimizer_ms_per_step": "optimizer",
    "unscoped_device_ms_per_step": "unscoped",
    "step_call_host_ms": SPAN_STEP,
    "wrap_step_prepare_host_ms": SPAN_WRAP_PREPARE,
}


class Op(NamedTuple):
    """One executed op of chip 0."""
    name: str       # the HLO instruction, as `trace_reduce` names it
    start: float
    end: float
    tf_op: str      # jax's name stack of the op (one per fusion); "" if none
    category: str   # XLA's `hlo_category`; "" if none


class Span(NamedTuple):
    """One host span: the program's (`hvd.`) or the benchmark loop's."""
    name: str
    start: float
    end: float
    thread: int             # the host line's id
    step: Optional[int]     # the `step` argument, where the span has one


@dataclasses.dataclass(frozen=True)
class RegionTrace:
    ops: tuple        # Op per executed op, chip 0
    programs: tuple   # trace_reduce.Event per executed program, chip 0
    spans: tuple      # Span per host event of the two vocabularies


# ------------------------------------------------------------ arithmetic

def region_of(tf_op: str) -> str:
    """The one region of an op, from its name stack, by precedence:
    optimizer > loss_head > backward > forward > unscoped."""
    if SCOPE_OPTIMIZER in tf_op:
        return "optimizer"
    if SCOPE_LOSS in tf_op or any(
            part in HEAD_MODULES for part in tf_op.split("/")):
        return "loss_head"
    if "transpose(" in tf_op:
        return "backward"
    if "jvp(" in tf_op:
        return "forward"
    return "unscoped"


def stack_prefix(op: Op, depth: int = 3) -> str:
    """What an op that no name reaches is filed under: the first `depth`
    components of its name stack, or its op family where it has none."""
    if not op.tf_op:
        return f"(no tf_op) {trace_reduce.family(op.name)}"
    return "/".join(op.tf_op.rstrip(":").split("/")[:depth])


@dataclasses.dataclass(frozen=True)
class Partition:
    """Chip 0's device time in one window of whole steps, by region."""
    steps: int
    seconds: dict            # region -> seconds, every region present
    busy_s: float            # union of the ops' intervals
    unscoped: tuple          # ((name-stack prefix, seconds), ...) heaviest first

    def ms_per_step(self) -> dict:
        return {r: s / self.steps * 1e3 for r, s in self.seconds.items()}


def partition(ops: Sequence[Op], window: tuple, steps: int,
              top: int = 10) -> Partition:
    """Every op of the window in exactly one region. Ops of one core do
    not overlap, so the regions sum to the busy time; a trace where they
    do not (to 0.1%) is not what this reader was written for."""
    lo, hi = window
    seconds = dict.fromkeys(REGIONS, 0.0)
    unscoped = collections.Counter()
    spans = []
    for op in ops:
        if op.end <= lo or op.start >= hi:
            continue
        start, end = max(op.start, lo), min(op.end, hi)
        region = region_of(op.tf_op)
        seconds[region] += end - start
        spans.append((start, end))
        if region == "unscoped":
            unscoped[stack_prefix(op)] += end - start
    busy = trace_reduce.covered(spans)
    total = sum(seconds.values())
    if abs(total - busy) > 1e-3 * max(busy, 1e-12):
        raise ValueError(
            f"the regions sum to {total:.6f} s but chip 0 was busy "
            f"{busy:.6f} s in the window: ops overlap")
    return Partition(steps=steps, seconds=seconds, busy_s=busy,
                     unscoped=tuple(unscoped.most_common(top)))


def nest(spans: Iterable[Span]) -> list:
    """(span, parent or None, self seconds) per span: a span's parent is
    the span that encloses it on the same thread, its self time its
    duration minus its children's."""
    out = []
    by_thread = collections.defaultdict(list)
    for span in spans:
        by_thread[span.thread].append(span)
    for thread in by_thread.values():
        # Parents first: by start, the longer span first at equal starts.
        thread.sort(key=lambda s: (s.start, -s.end))
        open_, rows = [], []      # stack of indices into rows
        for span in thread:
            while open_ and rows[open_[-1]][0].end <= span.start:
                open_.pop()
            parent = rows[open_[-1]][0] if open_ else None
            if open_:
                rows[open_[-1]][2] -= span.end - span.start
            rows.append([span, parent, span.end - span.start])
            open_.append(len(rows) - 1)
        out += [tuple(row) for row in rows]
    return out


def innermost_covering(gap: tuple, spans: Iterable[Span]) -> str:
    """The shortest program span that covers more than half of the gap,
    or "none"."""
    lo, hi = gap
    covering = [s for s in spans if s.name.startswith(PROGRAM_PREFIX)
                and min(s.end, hi) - max(s.start, lo) > (hi - lo) / 2]
    return min(covering, key=lambda s: s.end - s.start).name \
        if covering else "none"


def starting_in(window: tuple, spans: Iterable[Span]) -> list:
    """The spans that start in the window, in order of their starts."""
    return sorted((s for s in spans if window[0] <= s.start < window[1]),
                  key=lambda s: s.start)


def medians_ms(pairs: Iterable[tuple]) -> dict:
    """name -> median ms of the (name, seconds) pairs."""
    by_name = collections.defaultdict(list)
    for name, seconds in pairs:
        by_name[name].append(seconds)
    return {name: statistics.median(by_name[name]) * 1e3
            for name in sorted(by_name)}


# ---------------------------------------------------------------- tables

@dataclasses.dataclass(frozen=True)
class Regions:
    """What the names say about one traced window of whole steps."""
    window: tuple
    partition: Partition
    span_ms: dict         # span name -> median ms of those starting in the window
    span_self_ms: dict    # the same of their self times
    steps_numbered: tuple  # `step` arguments of the window's `hvd.step` spans
    enclosed: Optional[dict]  # `hvd.step` against the loop's `dispatch`
    idle_gaps: tuple      # ((loop span, program span, seconds), ...) longest first

    def metrics(self) -> dict:
        """name -> ms of `METRICS`, None where the trace holds nothing
        to read (a parent of the scopes reads regions all the same: its
        loss and optimizer fall under `forward` and `unscoped`)."""
        per_step = self.partition.ms_per_step()
        return {name: per_step[source] if source in REGIONS
                else self.span_ms.get(source)
                for name, source in METRICS.items()}

    def info(self) -> dict:
        return {
            "steps": self.partition.steps,
            "regions_ms_per_step": self.partition.ms_per_step(),
            "busy_ms_per_step": (self.partition.busy_s
                                 / self.partition.steps * 1e3),
            "unscoped_prefixes_ms_per_step": [
                [prefix, s / self.partition.steps * 1e3]
                for prefix, s in self.partition.unscoped],
            "host_span_median_ms": self.span_ms,
            "host_span_self_median_ms": self.span_self_ms,
            "hvd_step_numbers": list(self.steps_numbered),
            "hvd_step_in_dispatch": self.enclosed,
            "idle_gaps": [[loop, program, s * 1e3]
                          for loop, program, s in self.idle_gaps],
            "metrics": self.metrics(),
        }


def _enclosed(steps: Sequence[Span], spans: Sequence[Span]) -> Optional[dict]:
    """How the program's `hvd.step` spans `steps` sit in the loop's
    `dispatch` spans: how many lie inside exactly one, and the medians
    of the two."""
    around = [[d for d in spans if d.name == "dispatch"
               and d.thread == s.thread and d.start <= s.start
               and s.end <= d.end] for s in steps]
    if not any(around):
        return None
    return {"hvd_step": len(steps),
            "inside_one_dispatch": sum(len(ds) == 1 for ds in around),
            "hvd_step_median_ms": statistics.median(
                s.end - s.start for s in steps) * 1e3,
            "dispatch_median_ms": statistics.median(
                d.end - d.start for d in {d for ds in around
                                          for d in ds}) * 1e3}


def reduce(trace: RegionTrace, every: int, gaps: int = 5) -> Regions:
    """The regions and spans of the window `trace_reduce.reduce` takes:
    between the first step start and the last whole multiple of `every`
    steps after it. A span belongs to the window it starts in."""
    starts = trace_reduce.step_starts(trace.programs, every)
    window = starts[0], starts[-1]
    program = [s for s in trace.spans if s.name.startswith(PROGRAM_PREFIX)]
    inside = starting_in(window, program)
    steps = [s for s in inside if s.name == SPAN_STEP]
    busy = trace_reduce.merge((op.start, op.end) for op in trace.ops)
    idle = sorted(trace_reduce.subtract([window], busy),
                  key=lambda g: g[0] - g[1])[:gaps]
    loop = [Event(s.name, s.start, s.end) for s in trace.spans
            if s.name in LOOP_SPANS]
    return Regions(
        window=window,
        partition=partition(trace.ops, window, len(starts) - 1),
        span_ms=medians_ms((s.name, s.end - s.start) for s in inside),
        span_self_ms=medians_ms(
            (span.name, own) for span, _, own in nest(program)
            if window[0] <= span.start < window[1]),
        steps_numbered=tuple(s.step for s in steps),
        enclosed=_enclosed(steps, trace.spans),
        idle_gaps=tuple((trace_reduce.attribute(g, loop),
                         innermost_covering(g, program), g[1] - g[0])
                        for g in idle))


# ---------------------------------------------------------------- loader
#
# xplane.proto, as far as it is read (field numbers):
#   XSpace          planes=1
#   XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
#                   (both map<int64, message>: entries of key=1 value=2)
#   XLine           id=1 name=2 timestamp_ns=3 events=4
#   XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
#   XStat           metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
#   XEventMetadata  id=1 name=2 stats=5
#   XStatMetadata   id=1 name=2

def _varint(buf, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        else:
            if wire == 2:
                size, at = _varint(buf, at)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple:
    fields = dict(_fields(buf))
    return fields.get(1, 0), fields.get(2, b"")


def _name(buf) -> str:
    """Field 2 of an XEventMetadata or an XStatMetadata."""
    return next((_text(v) for n, v in _fields(buf) if n == 2), "")


def _stat(buf, stat_names: dict) -> tuple:
    """(name, value) of one XStat; a `ref` is a string kept among the
    plane's stat names."""
    name, value = "", None
    for number, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw, "")
        elif number in (3, 4):
            value = raw
        elif number == 5:
            value = _text(raw)
        elif number == 7:
            value = stat_names.get(raw, "")
    return name, value


def _plane(buf) -> tuple:
    """(name, lines, event metadata, stat names) of one XPlane, the
    nested messages still unparsed."""
    name, lines, events, stats = "", [], {}, {}
    for number, raw in _fields(buf):
        if number == 2:
            name = _text(raw)
        elif number == 3:
            lines.append(raw)
        elif number == 4:
            key, value = _map_entry(raw)
            events[key] = value
        elif number == 5:
            key, value = _map_entry(raw)
            stats[key] = _name(value)
    return name, lines, events, stats


def _line(buf) -> tuple:
    """(id, name, start of the line in ps, its events unparsed)."""
    line_id, name, t0_ps, events = 0, "", 0, []
    for number, raw in _fields(buf):
        if number == 1:
            line_id = raw
        elif number == 2:
            name = _text(raw)
        elif number == 3:
            t0_ps = raw * 1000
        elif number == 4:
            events.append(raw)
    return line_id, name, t0_ps, events


def _event(buf, t0_ps: int) -> tuple:
    """(metadata id, start s, end s, its stats unparsed)."""
    meta, offset, duration, stats = 0, 0, 0, []
    for number, raw in _fields(buf):
        if number == 1:
            meta = raw
        elif number == 2:
            offset = raw
        elif number == 3:
            duration = raw
        elif number == 4:
            stats.append(raw)
    start = (t0_ps + offset) * 1e-12
    return meta, start, start + duration * 1e-12, stats


def _metadata(buf, stat_names: dict) -> tuple:
    """(name, stats as a dict) of one XEventMetadata."""
    name, stats = "", {}
    for number, raw in _fields(buf):
        if number == 2:
            name = _text(raw)
        elif number == 5:
            key, value = _stat(raw, stat_names)
            stats[key] = value
    return name, stats


def _device_lines(lines, metadata: dict, stat_names: dict) -> Optional[tuple]:
    """(ops, programs) of one device plane, None if it lacks a line."""
    found = {}
    for raw in lines:
        _, name, t0_ps, events = _line(raw)
        if name in (trace_reduce.OPS_LINE, trace_reduce.PROGRAMS_LINE):
            found[name] = (t0_ps, events)
    if len(found) < 2:
        return None
    known = {}

    def meta(key):
        if key not in known:
            known[key] = _metadata(metadata.get(key, b""), stat_names)
        return known[key]

    ops = []
    t0_ps, events = found[trace_reduce.OPS_LINE]
    for raw in events:
        key, start, end, _ = _event(raw, t0_ps)
        name, stats = meta(key)
        ops.append(Op(name, start, end, stats.get("tf_op") or "",
                      stats.get("hlo_category") or ""))
    t0_ps, events = found[trace_reduce.PROGRAMS_LINE]
    programs = []
    for raw in events:
        key, start, end, _ = _event(raw, t0_ps)
        programs.append(Event(meta(key)[0], start, end))
    return tuple(ops), tuple(programs)


def _host_spans(lines, metadata: dict, stat_names: dict) -> list:
    names = {key: _name(raw) for key, raw in metadata.items()}
    wanted = {key for key, name in names.items()
              if name.startswith(PROGRAM_PREFIX) or name in LOOP_SPANS}
    spans = []
    for raw in lines:
        thread, _, t0_ps, events = _line(raw)
        for ev in events:
            key, start, end, stats = _event(ev, t0_ps)
            if key not in wanted:
                continue
            args = dict(_stat(s, stat_names) for s in stats)
            step = args.get("step")
            spans.append(Span(names[key], start, end, thread,
                              int(step) if step is not None else None))
    return spans


def load(path: str) -> RegionTrace:
    """Read an `.xplane.pb`: chip 0's ops with their name stacks, its
    programs, and the host spans of the two vocabularies."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    chips, spans = {}, []
    for number, raw in _fields(space):
        if number != 1:
            continue
        name, lines, metadata, stat_names = _plane(raw)
        m = trace_reduce.DEVICE_PLANE.match(name)
        if m:
            found = _device_lines(lines, metadata, stat_names)
            if found:
                chips[int(m.group(1))] = found
        elif name.startswith(trace_reduce.HOST_PLANE_PREFIX):
            spans += _host_spans(lines, metadata, stat_names)
    if not chips:
        raise ValueError(
            f"{path}: no /device:TPU:<n> plane with the lines "
            f"{trace_reduce.OPS_LINE!r} and {trace_reduce.PROGRAMS_LINE!r}")
    ops, programs = chips[min(chips)]
    return RegionTrace(ops=ops, programs=programs, spans=tuple(spans))


if __name__ == "__main__":
    every = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    print("info: " + json.dumps(reduce(load(sys.argv[1]), every).info()))
