"""One cell, one run: the training loop a user writes, timed.

Everything that belongs to one cell is data found by name, starting
from the index (BENCHMARK.json): the cell's configuration and traffic
files, its trainer and reference modules, its per-layer metric readers.
Nothing here knows a cell's name. README.md says what each file holds
and how a later PR adds one.

The loop (the same for every cell): parameters made on the device from
the seed; a pool of token batches made on the host from the seed; each
step puts the next batch on the device and calls the trainer's step
without waiting; every `log_every` steps the losses are fetched, as a
logging loop does, and that fetch ends a timed interval. An interval's
time over `log_every` is one sample of step time.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import importlib
import importlib.metadata
import json
import math
import pathlib
import statistics
import tempfile
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, trace_reduce, trace_regions

SPANS = ("feed", "dispatch", "log_fetch")
WARMUP_INTERVALS = 2
# Three traced intervals hold two whole ones between step starts.
TRACE_INTERVALS = 3
LOSS_AT_STEP = 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GIB = 2.0 ** 30


# ------------------------------------------------------------- the files

@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict       # the configuration's file
    dims: dict         # the model's keyword arguments, as run
    traffic: dict      # the traffic mix's file
    expect: dict       # the cell's recorded loss and band
    end_to_end: dict   # the metrics this cell reports, name -> unit
    per_layer: dict

    @property
    def phases(self) -> list:
        """The loops of one run, in order: an optional baseline layout
        for the first `share` of the window, then the cell's own."""
        keys = ("seq", "batch_per_chip")
        main = {"name": "main", "mesh": self.traffic["mesh"],
                **{k: self.traffic[k] for k in keys}}
        base = self.traffic.get("baseline")
        if base is None:
            return [dict(main, share=1.0)]
        return [{"name": "baseline", "mesh": base["mesh"],
                 "share": base["share"], **{k: main[k] for k in keys}},
                dict(main, share=1.0 - base["share"])]


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(index_path: pathlib.Path, name: str) -> Cell:
    """The cell `name` of the index at `index_path`. The configuration's
    file is where the index says; traffic and the cell's expectations
    are `traffic/<name>.json` and `workloads/<name>.json` under the
    index's first path."""
    index = _read(index_path)
    root = index_path.parent
    data = root / index["paths"][0]
    try:
        entry = next(w for w in index["workloads"] if w["name"] == name)
    except StopIteration:
        known = [w["name"] for w in index["workloads"]]
        raise KeyError(f"no workload {name!r} in {index_path}; "
                       f"known: {known}") from None
    config = _read(root / next(
        c["file"] for c in index["configs"] if c["name"] == entry["config"]))
    dims = {kw: config[key] for kw, key in config["model_kwargs"].items()}
    dims.update(config["model_options"])
    return Cell(
        name=name, chips=entry["chips"], config=config, dims=dims,
        traffic=_read(data / "traffic" / f"{entry['traffic']}.json"),
        expect=_read(data / "workloads" / f"{name}.json"),
        end_to_end={m["name"]: m["unit"] for m in index["end_to_end"]
                    if _applies(m, name)},
        per_layer={m["name"]: m["unit"] for m in index["per_layer"]
                   if _applies(m, name)},
    )


def named(dotted: str):
    """`module.function` under this package, e.g. `flops.transformer`."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(f"benchmark.{module}"), attr)


def make_model(cell: Cell):
    from horovod_tpu.models import get_model

    dtypes = {k: jnp.dtype(v) for k, v in cell.config["model_dtypes"].items()}
    return get_model(cell.config["registry"]).make_model(**cell.dims,
                                                         **dtypes)


# --------------------------------------------------------------- the loop

class JaxEvents:
    """What jax reports about its own work while this is open: seconds
    per kind of work (tracing, lowering, compiling or loading from the
    cache) and counts (programs, cache hits and misses)."""

    def __init__(self):
        self.seconds = collections.Counter()
        self.counts = collections.Counter()

    def _duration(self, event: str, duration: float, **_):
        self.seconds[event] += duration
        self.counts[event] += 1

    def _event(self, event: str, **_):
        self.counts[event] += 1

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the cache."""
        return self.counts[COMPILE_EVENT]

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def summary(self) -> dict:
        return {"seconds": {k.rsplit("/", 1)[-1]: v
                            for k, v in self.seconds.items()},
                "counts": {k.rsplit("/", 1)[-1]: v
                           for k, v in self.counts.items()}}


@dataclasses.dataclass
class Loop:
    """One trainer state stepped over the pool, interval by interval."""
    trainer: Any
    pool: list
    log_every: int
    devices: list
    state: Any = None
    steps: int = 0       # of this state
    attempted: int = 0   # of the run
    failed: int = 0
    losses: dict = dataclasses.field(default_factory=dict)   # step -> loss
    step_s: list = dataclasses.field(default_factory=list)   # per interval
    dispatch_s: list = dataclasses.field(default_factory=list)
    hbm_bytes: list = dataclasses.field(default_factory=list)  # per device
    first_call_s: float = 0.0
    unmoved: int = 0     # leaves the first step left as they were

    def start(self):
        """Make the state and take the first step alone, on the clock:
        that call compiles the step or loads it from the cache. Off the
        clock, the parameters' checksums before and after it."""
        self.state = jax.block_until_ready(self.trainer.init())
        before = jax.block_until_ready(
            correct.leaf_checksums(self.trainer.params(self.state)))
        t0 = time.perf_counter()
        self.interval(steps=1)
        self.first_call_s = time.perf_counter() - t0
        self.unmoved = correct.leaves_unmoved(
            before, correct.leaf_checksums(self.trainer.params(self.state)))

    def interval(self, steps: Optional[int] = None, record: bool = False):
        t0 = time.perf_counter()
        losses = []
        for _ in range(steps or self.log_every):
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("feed"):
                batch = self.trainer.put(self.pool[self.steps % len(self.pool)])
            with jax.profiler.TraceAnnotation("dispatch"):
                self.state, loss = self.trainer.step(self.state, batch)
            if record:
                self.dispatch_s.append(time.perf_counter() - t1)
            self.steps += 1
            self.attempted += 1
            losses.append(loss)
        if record:
            self.hbm_bytes = [max(*pair) for pair in zip(
                self.hbm_bytes or [0] * len(self.devices),
                _hbm_bytes(self.devices))]
        with jax.profiler.TraceAnnotation("log_fetch"):
            values = [float(v) for v in jax.device_get(losses)]
        if record:
            self.step_s.append((time.perf_counter() - t0) / len(values))
        for i, value in enumerate(values, self.steps - len(values) + 1):
            self.losses[i] = value
            self.failed += not math.isfinite(value)

    def drop(self):
        self.state, self.steps = None, 0


def _hbm_bytes(devices) -> list:
    """What each chip holds now: live arrays plus the region the runtime
    reserves for the loaded programs' temporaries. On libtpu 0.0.34 a
    step's temporaries are in `bytes_reserved`, not in `bytes_in_use`
    (PERF.md section 6, PR 22), and no counter keeps the peak of the
    sum, so the loop samples it while an interval's steps are in flight."""
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
            for s in stats]


def _pool(cell: Cell, trainer, seed: int) -> list:
    rng = np.random.default_rng(seed)
    shape = (trainer.global_batch, cell.traffic["seq"])
    return [rng.integers(0, cell.dims["vocab_size"], size=shape,
                         dtype=np.int32)
            for _ in range(cell.traffic["pool"])]


def _trace_options():
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the interpreter's own frames
    options.host_tracer_level = 2     # TraceAnnotation spans
    return options


def _measure(loop: Loop, seconds: float, trace_dir: Optional[str]):
    """Whole intervals until `seconds` have passed; with `trace_dir`, the
    middle TRACE_INTERVALS run under the profiler and are no sample."""
    t0 = time.perf_counter()
    traced = trace_dir is None
    while (elapsed := time.perf_counter() - t0) < seconds:
        if not traced and elapsed >= seconds / 2:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options())
            try:
                for _ in range(TRACE_INTERVALS):
                    loop.interval()
            finally:
                jax.profiler.stop_trace()
            traced = True
        else:
            loop.interval(record=True)


# ------------------------------------------------------------ the results

@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    chips: int
    tokens_per_step: int
    step_s: tuple        # samples: interval seconds over log_every
    dispatch_s: tuple    # samples: host seconds for one feed + step call
    first_call_s: float

    @property
    def tokens_per_s_per_chip(self) -> float:
        return (self.tokens_per_step / statistics.median(self.step_s)
                / self.chips)


@dataclasses.dataclass(frozen=True)
class Context:
    """What a per-layer metric's `compute(ctx)` may read."""
    cell: Cell
    peaks: Any
    phases: dict                              # name -> Phase
    tables: Optional[trace_reduce.Tables]     # None in an untraced run
    trace_file: Optional[str] = None          # the traced run's .xplane.pb

    @functools.cached_property
    def regions(self) -> Optional[trace_regions.Regions]:
        """The same window by the names the program gives itself
        (trace_regions.py), read from the file on first use; None in an
        untraced run."""
        if self.trace_file is None:
            return None
        return trace_regions.reduce(trace_regions.load(self.trace_file),
                                    self.cell.traffic["log_every"])


def _quantile(samples, q: float) -> float:
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def _info(**fields):
    print("info: " + json.dumps(fields), flush=True)


def versions() -> dict:
    import jaxlib

    found = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        found["libtpu"] = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        found["libtpu"] = None
    return found


def _set_up(cell: Cell, devices, seed: int, stages: dict):
    """Every phase's trainer built, its step called once on the clock
    and its loop warmed, last phase first, so that the phase the window
    opens with is the one whose state is alive when it opens (the
    others' states are dropped again); with that phase's parameters, the
    program compared with the reference. Returns the loops by phase name
    and the reference errors; `stages` receives the seconds of each
    part."""
    model = make_model(cell)
    build = importlib.import_module(
        f"benchmark.trainers.{cell.traffic['trainer']}").build
    reference = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    first = cell.phases[0]["name"]
    loops, errors = {}, None
    for phase in reversed(cell.phases):
        label, at = phase["name"], time.perf_counter()
        on = devices[:math.prod(phase["mesh"].values())]
        trainer = build(model, phase, on, seed)
        loop = loops[label] = Loop(trainer, _pool(cell, trainer, seed),
                                   cell.traffic["log_every"], on)
        loop.start()
        stages[f"{label}: build, init, first call"] = time.perf_counter() - at
        if label == first:
            at = time.perf_counter()
            errors = correct.measure_against_reference(
                trainer.objective, reference, trainer.params(loop.state),
                cell.dims, cell.traffic["seq"], seed,
                cell.config["grad_leaves"])
            stages["reference comparison"] = time.perf_counter() - at
        at = time.perf_counter()
        for _ in range(WARMUP_INTERVALS):
            loop.interval()
        stages[f"{label}: warm-up"] = time.perf_counter() - at
        if label != first:
            loop.drop()
    return loops, errors


def _reduce_trace(kept: str, log_every: int) -> tuple:
    """The traced run's file and its tables."""
    path = str(next(pathlib.Path(kept).rglob("*.xplane.pb")))
    return path, trace_reduce.reduce(trace_reduce.load(path, SPANS),
                                     log_every)


def _compared(errors: dict, unmoved: int, loss: float, expect: dict,
              compiles: int, not_finite: int, checksums: list) -> dict:
    """Every number `correct` compares, beside its limit."""
    rows = {name: {"value": value, "limit": correct.tolerance(name)}
            for name, value in errors.items()}
    rows["leaves_unmoved"] = {"value": unmoved, "limit": 0}
    rows["loss_after_20"] = {"value": loss, "limit": [
        expect["loss_after_20"] - expect["loss_band"],
        expect["loss_after_20"] + expect["loss_band"]]}
    rows["compiles_in_window"] = {"value": compiles, "limit": 0}
    rows["losses_not_finite"] = {"value": not_finite, "limit": 0}
    if checksums:
        rows["distinct_replica_checksums"] = {
            "value": len(set(checksums)), "limit": 1}
    return rows


def run_cell(index_path, name: str, *, seed: int, seconds: float,
             trace: bool, devices, peaks, t0: float,
             trace_dir: Optional[str] = None) -> dict:
    """Run cell `name` once and return the contract's result object.
    `devices` are the chips to use (the first `chips` of them), `peaks`
    theirs, `t0` the `time.perf_counter()` of process start. A kept
    `trace_dir` receives the profiler's files of a traced run."""
    cell = load_cell(pathlib.Path(index_path), name)
    if len(devices) < cell.chips:
        raise RuntimeError(f"cell {name} needs {cell.chips} chips; jax "
                           f"reports {len(devices)}")
    devices = list(devices[:cell.chips])
    flops_per_token = named(cell.config["flops_per_token"])(
        cell.dims, cell.traffic["seq"])
    log_every = cell.traffic["log_every"]

    stages = {"process start, imports, devices": time.perf_counter() - t0}
    software = versions()
    with JaxEvents() as in_setup:
        loops, errors = _set_up(cell, devices, seed, stages)
    _info(cell=name, seed=seed, versions=software,
          flops_per_token=flops_per_token, reference_errors=errors,
          setup_stages_s=stages, setup_jax=in_setup.summary())

    # The window: each phase for its share, one state alive at a time.
    # A traced run's files stay until the per-layer readers have run:
    # in `trace_dir` where one is given, else in a directory of its own
    # that goes with the run (also when the run ends in an error).
    scratch = (tempfile.TemporaryDirectory(prefix="trace-")
               if trace and trace_dir is None else None)
    kept = trace_dir or (scratch.name if scratch else None)
    setup_s = time.perf_counter() - t0
    with JaxEvents() as in_window:
        for phase in cell.phases:
            loop = loops[phase["name"]]
            if loop.state is None:
                loop.state = jax.block_until_ready(loop.trainer.init())
            _measure(loop, seconds * phase["share"],
                     kept if phase["name"] == "main" else None)
            if loop is not loops["main"]:
                loop.drop()
    main = loops["main"]

    # After the window.
    checksums = main.trainer.checksums(main.state) if cell.chips > 1 else []
    for loop in loops.values():
        loop.trainer.close()
    trace_file, tables = (_reduce_trace(kept, log_every) if trace
                          else (None, None))
    phases = {
        name: Phase(
            name=name, chips=len(loop.devices),
            tokens_per_step=loop.trainer.global_batch * cell.traffic["seq"],
            step_s=tuple(loop.step_s), dispatch_s=tuple(loop.dispatch_s),
            first_call_s=loop.first_call_s)
        for name, loop in loops.items()}
    for p in phases.values():
        _info(phase=p.name, intervals=len(p.step_s), log_every=log_every,
              step_ms_median=statistics.median(p.step_s) * 1e3,
              step_ms_p90=_quantile(p.step_s, 0.9) * 1e3,
              step_ms_max=max(p.step_s) * 1e3,
              first_call_s=p.first_call_s)

    loss = main.losses.get(LOSS_AT_STEP, math.nan)
    unmoved = sum(loop.unmoved for loop in loops.values())
    checks = {
        "platform_is_tpu": devices[0].platform == "tpu",
        "no_compile_in_window": in_window.programs == 0,
        "losses_finite": all(loop.failed == 0 for loop in loops.values()),
        "agrees_with_reference": not correct.beyond_tolerance(errors),
        "state_moves": unmoved == 0,
        "loss_in_band": correct.loss_in_band(
            loss, cell.expect["loss_after_20"], cell.expect["loss_band"]),
        "replicas_agree": len(set(checksums)) <= 1,
    }
    _info(checks=checks, compiles_in_window=in_window.programs,
          loss_after_20=loss, checksums=checksums,
          memory_stats={str(d.id): d.memory_stats() for d in devices})

    result = {
        "correct": all(checks.values()),
        "attempted": sum(loop.attempted for loop in loops.values()),
        "failed": sum(loop.failed for loop in loops.values()),
    }
    compared = _compared(errors, unmoved, loss, cell.expect,
                         in_window.programs, result["failed"], checksums)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  b for loop in loops.values() for b in loop.hbm_bytes),
              "versions": software}
    if not trace:
        mp = phases["main"]
        # Chips that ran the cell's own layout only: the baseline's chips
        # also hold what its program reserved.
        shared = len(loops["baseline"].devices) if "baseline" in loops else 0
        values = {
            "tokens_per_s_per_chip": mp.tokens_per_s_per_chip,
            "mfu": (mp.tokens_per_s_per_chip * flops_per_token
                    / peaks.bf16_flops_per_s),
            "peak_hbm_gib": max(main.hbm_bytes[shared:]) / GIB,
            "setup_s": setup_s,
        }
        if "baseline" in phases:
            values["scaling_efficiency"] = (
                mp.tokens_per_s_per_chip
                / phases["baseline"].tokens_per_s_per_chip)
        result["metrics"] = {k: {"value": values[k], "unit": unit}
                             for k, unit in cell.end_to_end.items()}
        result["device"] = device
        result["compared"] = compared
        return result

    _info(heaviest_single_ops_ms_per_step=[
        [text, s / tables.steps * 1e3] for text, s in tables.heaviest])
    ctx = Context(cell=cell, peaks=peaks, phases=phases, tables=tables,
                  trace_file=trace_file)
    result["metrics"] = {}
    for metric, unit in cell.per_layer.items():
        value = importlib.import_module(
            f"benchmark.layer_metrics.{metric}").compute(ctx)
        if value is not None:
            result["metrics"][metric] = {"value": value, "unit": unit}
    if scratch is not None:
        scratch.cleanup()
    result["device"] = dict(device, busy_s=tables.busy_s_mean,
                            window_s=tables.window_s)
    result["breakdown"] = {
        "device_ops": tables.top_ops(10),
        "idle_gaps": [[span, s] for span, s in tables.idle_gaps],
    }
    result["compared"] = compared
    return result
