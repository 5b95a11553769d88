"""From a profiler trace to the tables the per-layer metrics read.

The arithmetic works on plain `Event(name, start, end)` tuples in
seconds, so it is tested on a synthetic list with known answers; the
loader turns `jax.profiler.ProfileData` (an `.xplane.pb`) into those
tuples and is tested on a small trace recorded on the chip
(tests/benchmarking/test_benchmark_trace_reduce.py).

What a TPU trace looks like on jax 0.9.0 / libtpu 0.0.34 (looked at by
hand in PR 22; `python -m benchmark.trace_reduce <file>` prints the
same summary). One plane per chip, `/device:TPU:<n>`, with the lines

* `XLA Modules`: one event per executed program, `jit_train_step(<id>)`
  (`jit_local_fn(<id>)` for a `wrap_step` step);
* `XLA Ops`: one event per executed HLO op, named by the whole HLO
  instruction, `%fusion.12 = f32[...] fusion(...)`. The op's own name is
  the token after `%`: Pallas kernels carry their `name=`
  (`flash_attention_fwd.3`), collectives XLA's op names. Ops of one
  core do not nest or overlap. An async op appears here twice, as a
  short `<op>-start.N` and as the wait `<op>-done.N`;
* `Async XLA Ops`: one event per async op from its start to the end of
  its done, named by the start instruction — what is in flight.

Host threads are lines of the plane `/host:CPU`; the benchmark's
`TraceAnnotation` spans appear there under their names. All planes share
one clock.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import statistics
import sys
from typing import Iterable, NamedTuple, Optional, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
PROGRAMS_LINE = "XLA Modules"

# XLA's collective op names, with the halves of their async forms.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")


class Event(NamedTuple):
    name: str
    start: float
    end: float


@dataclasses.dataclass(frozen=True)
class DeviceTrace:
    ops: tuple            # Event per executed op
    programs: tuple       # Event per executed program
    async_ops: tuple = ()  # Event per async op, start to end of done


@dataclasses.dataclass(frozen=True)
class Trace:
    devices: dict     # chip number -> DeviceTrace
    host_spans: tuple  # the benchmark's own spans, by `span_names`


# ------------------------------------------------------------ arithmetic

def merge(intervals: Iterable[tuple]) -> list:
    """Sorted, disjoint (start, end) covering the same points."""
    out: list = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def covered(intervals: Iterable[tuple]) -> float:
    """Seconds in the union of the intervals."""
    return sum(e - s for s, e in merge(intervals))


def clip(events: Iterable[Event], window: tuple) -> list:
    lo, hi = window
    return [Event(ev.name, max(ev.start, lo), min(ev.end, hi))
            for ev in events if ev.end > lo and ev.start < hi]


def spans_of(events: Iterable[Event]) -> list:
    return [(ev.start, ev.end) for ev in events]


def subtract(intervals: Iterable[tuple], holes: Iterable[tuple]) -> list:
    """The parts of `intervals` that no hole covers."""
    holes = merge(holes)
    out = []
    for start, end in merge(intervals):
        at = start
        for h0, h1 in holes:
            if h1 <= at or h0 >= end:
                continue
            if h0 > at:
                out.append((at, h0))
            at = max(at, h1)
        if at < end:
            out.append((at, end))
    return out


def op_name(text: str) -> str:
    """The op's own name. On the TPU an op's event carries the whole HLO
    instruction, `%fusion.12 = f32[...] fusion(...)`."""
    return text[1:].split(" ", 1)[0] if text.startswith("%") else text


def family(name: str) -> str:
    """An op's name without its instance number: `fusion.12` -> `fusion`."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def step_program(programs: Sequence[Event]) -> str:
    """The program that took most device time: the train step."""
    total = collections.Counter()
    for ev in programs:
        total[family(ev.name)] += ev.end - ev.start
    if not total:
        raise ValueError("the trace holds no executed program")
    return total.most_common(1)[0][0]


def step_starts(programs: Sequence[Event], every: int) -> list:
    """The starts of the traced steps that bound whole logging intervals:
    the first one and as many whole multiples of `every` steps after it
    as the trace holds, so that the window between the first and the
    last holds the same number of log fetches per step as the run."""
    name = step_program(programs)
    starts = sorted(ev.start for ev in programs if family(ev.name) == name)
    steps = (len(starts) - 1) // every * every
    if steps < every:
        raise ValueError(
            f"the trace holds {len(starts)} starts of {name}, fewer than "
            f"one logging interval of {every} steps and the next start")
    return starts[:steps + 1]


def is_collective(name: str) -> bool:
    return COLLECTIVE.match(op_name(name)) is not None


def collective_intervals(device: DeviceTrace) -> list:
    """One (start, end) per collective in flight: a synchronous op's own
    event, an async one's from its start to the end of its done."""
    sync = [ev for ev in device.ops
            if (m := COLLECTIVE.match(op_name(ev.name))) and not m.group(2)]
    return sorted(spans_of(sync) + spans_of(
        ev for ev in device.async_ops if is_collective(ev.name)))


def attribute(gap: tuple, spans: Sequence[Event]) -> str:
    """The host span that covers most of the gap, or "none"."""
    best, best_overlap = "none", 0.0
    for ev in spans:
        overlap = min(ev.end, gap[1]) - max(ev.start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = ev.name, overlap
    return best


# ---------------------------------------------------------------- tables

@dataclasses.dataclass(frozen=True)
class Tables:
    """One traced window of whole steps on chip 0 (collectives and the
    busy average also over the other chips)."""
    steps: int
    window_s: float
    busy_s: float                 # union of op intervals, chip 0
    busy_s_mean: float            # the same, averaged over the chips
    step_period_s: float          # median distance between step starts
    op_seconds: dict              # op family -> seconds in the window
    heaviest: tuple               # ((one op's instruction, seconds), ...)
    collective_s: float           # chip 0
    collective_exposed_s: float   # ... while no other op ran on chip 0
    idle_gaps: tuple              # ((host span, seconds), ...) longest first

    def seconds_of(self, *families: str) -> Optional[float]:
        """Summed seconds of the named op families, None if none ran."""
        found = [self.op_seconds[f] for f in families if f in self.op_seconds]
        return sum(found) if found else None

    def top_ops(self, n: int = 10) -> list:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds] for name, seconds in top]


def reduce(trace: Trace, every: int, gaps: int = 5) -> Tables:
    """The tables of the window of whole steps (multiples of `every`)
    that the trace holds."""
    chip0 = trace.devices[min(trace.devices)]
    starts = step_starts(chip0.programs, every)
    window = lo, hi = starts[0], starts[-1]
    ops = clip(chip0.ops, window)
    op_seconds, instances = collections.Counter(), collections.Counter()
    for ev in ops:
        op_seconds[family(ev.name)] += ev.end - ev.start
        instances[ev.name] += ev.end - ev.start
    collectives = [(max(s, lo), min(e, hi))
                   for s, e in collective_intervals(chip0)
                   if e > lo and s < hi]
    compute = spans_of(ev for ev in ops if not is_collective(ev.name))
    busy = merge(spans_of(ops))
    idle = sorted(subtract([window], busy), key=lambda g: g[0] - g[1])[:gaps]
    return Tables(
        steps=len(starts) - 1,
        window_s=hi - lo,
        busy_s=covered(busy),
        busy_s_mean=statistics.fmean(
            covered(spans_of(clip(dev.ops, window)))
            for dev in trace.devices.values()),
        step_period_s=statistics.median(
            b - a for a, b in zip(starts, starts[1:])),
        op_seconds=dict(op_seconds),
        heaviest=tuple((text[:200], s)
                       for text, s in instances.most_common(10)),
        collective_s=covered(collectives),
        collective_exposed_s=covered(subtract(collectives, compute)),
        idle_gaps=tuple((attribute(g, trace.host_spans), g[1] - g[0])
                        for g in idle),
    )


# ---------------------------------------------------------------- loader

def _events(line) -> tuple:
    return tuple(Event(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                 for ev in line.events)


def load(path: str, span_names: Sequence[str]) -> Trace:
    """Read an `.xplane.pb` with nothing but jax."""
    from jax.profiler import ProfileData

    devices, host_spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines and PROGRAMS_LINE in lines:
                devices[int(m.group(1))] = DeviceTrace(
                    ops=_events(lines[OPS_LINE]),
                    programs=_events(lines[PROGRAMS_LINE]),
                    async_ops=_events(lines[ASYNC_LINE])
                    if ASYNC_LINE in lines else ())
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                host_spans += [ev for ev in _events(line)
                               if ev.name in span_names]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane with the lines "
                         f"{OPS_LINE!r} and {PROGRAMS_LINE!r}")
    return Trace(devices=devices, host_spans=tuple(host_spans))


def describe(path: str, top: int = 25) -> str:
    """What a trace file holds, for reading by hand before (re)writing
    a reduction: planes, lines, event counts and the heaviest names."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            first = last = None
            for ev in line.events:
                total[family(ev.name)] += ev.duration_ns
                count[family(ev.name)] += 1
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                last = end if last is None else max(last, end)
            if not count:
                continue
            out.append(f"  LINE {line.name!r}: {sum(count.values())} events, "
                       f"{first * 1e-6:.3f}..{last * 1e-6:.3f} ms")
            for name, ns in total.most_common(top):
                out.append(f"    {ns * 1e-6:10.3f} ms  x{count[name]:<6} "
                           f"{name[:90]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
