"""The reader of the program's own names (benchmark/trace_regions.py):
the partition and the span arithmetic on synthetic lists whose answers
are known by construction; the wire-format loader on three traces
recorded on the chip (TPU v5e, jax 0.9.0 / libtpu 0.0.34, tiny cells of
tests/benchmarking/cells/, the 15 traced steps of a `--trace 1` run):
`tiny_step` from before the program named anything (`tiny-gspmd-1c`,
PR 22), and from after (PR 25) `tiny_gspmd_step` (the same cell:
`make_train_step`, both scopes, `hvd.step`) and `tiny_hvd_step`
(`tiny-hvd-1c`: `jit_local_fn`, `wrap_step`'s spans)."""
import gzip
import json
import pathlib
import subprocess
import sys

import pytest

from benchmark import harness, trace_reduce, trace_regions
from benchmark.trace_reduce import Event
from benchmark.trace_regions import Op, RegionTrace, Span
from horovod_tpu.common import tracing

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
RECORDED = {"before": DATA / "tiny_step.xplane.pb.gz",
            "gspmd": DATA / "tiny_gspmd_step.xplane.pb.gz",
            "hvd": DATA / "tiny_hvd_step.xplane.pb.gz"}

MODEL = "jit(train_step)/jvp(TransformerLM)"
BACK = "jit(train_step)/transpose(jvp(TransformerLM))"


# ------------------------------------------------------------ vocabulary

def test_the_readers_vocabulary_is_the_programs():
    for name in ("SCOPE_LOSS", "SCOPE_OPTIMIZER", "SPAN_STEP",
                 "SPAN_WRAP_PREPARE", "SPAN_WRAP_BUILD", "SPAN_WRAP_CALL"):
        assert getattr(trace_regions, name) == getattr(tracing, name), name
        assert getattr(tracing, name).startswith(
            trace_regions.PROGRAM_PREFIX)
    assert trace_regions.LOOP_SPANS == harness.SPANS
    # Every metric reads a region or a span that exists.
    spans = {trace_regions.SPAN_STEP, trace_regions.SPAN_WRAP_PREPARE}
    assert set(trace_regions.METRICS.values()) == (
        set(trace_regions.REGIONS) | spans)


def test_the_reader_imports_neither_the_program_nor_tensorflow():
    code = ("import sys; import benchmark.trace_regions; "
            "print([m for m in sys.modules if m.split('.')[0] in "
            "('horovod_tpu', 'tensorflow', 'jax')])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, check=True).stdout
    assert json.loads(out.replace("'", '"')) == []


# ------------------------------------------------------------- partition

@pytest.mark.parametrize("tf_op,region", [
    # The precedence, one rule at a time.
    (f"{MODEL}/stack/layer_0/mlp/wi/dot_general:", "forward"),
    (f"{BACK}/stack/layer_0/mlp/wi/dot_general:", "backward"),
    (f"{MODEL}/lm_head/dot_general:", "loss_head"),
    (f"{BACK}/mlm_head/transpose:", "loss_head"),
    ("jit(train_step)/jvp(hvd.loss)/reduce_sum:", "loss_head"),
    ("jit(train_step)/transpose(jvp(hvd.loss))/mul:", "loss_head"),
    ("jit(train_step)/hvd.optimizer/add:", "optimizer"),
    # XLA fused a weight gradient under an AdamW root, or the other way.
    (f"{BACK}/lm_head/hvd.optimizer/mul:", "optimizer"),
    ("jit(local_fn)/jit(train_step)/hvd.optimizer/sqrt:", "optimizer"),
    # A module that merely has `lm_head` in its name is not the head.
    (f"{MODEL}/stack/my_lm_head_adapter/dot_general:", "forward"),
    ("jit(train_step)/add:", "unscoped"),
    ("", "unscoped"),
])
def test_an_op_falls_in_one_region_by_precedence(tf_op, region):
    assert trace_regions.region_of(tf_op) == region


def _ops(step_start: float) -> list:
    """One synthetic step of 10 units, 8 busy, back to back."""
    s = step_start

    def op(name, a, b, tf_op):
        return Op(f"%{name} = f32[8] fusion(f32[8] %p)", s + a, s + b, tf_op,
                  "loop fusion")
    return [
        op("fusion.1", 0.0, 2.0, f"{MODEL}/stack/layer_0/attn/qkv/dot_general:"),
        op("fusion.2", 2.0, 2.5, f"{MODEL}/lm_head/dot_general:"),
        op("fusion.3", 2.5, 3.0, "jit(train_step)/jvp(hvd.loss)/reduce_max:"),
        op("fusion.4", 3.0, 3.5, f"{BACK}/lm_head/dot_general:"),
        op("fusion.5", 3.5, 6.5, f"{BACK}/stack/layer_0/mlp/wo/dot_general:"),
        op("fusion.6", 6.5, 7.5, "jit(train_step)/hvd.optimizer/add:"),
        op("copy-done.7", 7.5, 7.8, ""),
        op("fusion.8", 7.8, 8.0, "jit(train_step)/add:"),
    ]


def test_partition_by_hand():
    starts = [0.0, 10.0, 20.0, 30.0]
    ops = [op for s in starts for op in _ops(s)]
    # The window cuts the first step's first op in half and ends before
    # the last step.
    parts = trace_regions.partition(ops, (1.0, 30.0), steps=3)
    assert parts.seconds == pytest.approx({
        "forward": 1.0 + 2 * 2.0, "loss_head": 3 * 1.5, "backward": 3 * 3.0,
        "optimizer": 3 * 1.0, "unscoped": 3 * 0.5})
    assert list(parts.seconds) == list(trace_regions.REGIONS)
    assert parts.busy_s == pytest.approx(3 * 8.0 - 1.0)
    assert sum(parts.seconds.values()) == pytest.approx(parts.busy_s)
    assert parts.ms_per_step()["optimizer"] == pytest.approx(1e3)
    # What no name reaches is listed by what little it says of itself.
    assert parts.unscoped == (
        ("(no tf_op) copy-done", pytest.approx(0.9)),
        ("jit(train_step)/add", pytest.approx(0.6)))


def test_partition_refuses_ops_that_overlap():
    ops = _ops(0.0) + [Op("%all-reduce-done.1", 5.0, 7.0, "", "all-reduce")]
    with pytest.raises(ValueError, match="ops overlap"):
        trace_regions.partition(ops, (0.0, 10.0), steps=1)


def test_stack_prefix_keeps_three_components():
    op = Op("%fusion.3", 0, 1, "jit(local_fn)/jit(train_step)/mul/x/y:", "")
    assert trace_regions.stack_prefix(op) == "jit(local_fn)/jit(train_step)/mul"
    assert trace_regions.stack_prefix(op._replace(tf_op="")) == (
        "(no tf_op) fusion")


# ----------------------------------------------------------------- spans

def _call(t: float, step: int, thread: int = 1) -> list:
    """One `wrap_step` call of 10 units inside a `dispatch` of 11."""
    return [
        Span("dispatch", t - 0.5, t + 10.5, thread, None),
        Span("hvd.step", t, t + 10.0, thread, step),
        Span("hvd.wrap_step.prepare", t + 1.0, t + 3.0, thread, None),
        Span("hvd.wrap_step.call", t + 3.0, t + 9.5, thread, None),
    ]


def test_nesting_and_self_time_on_one_thread():
    spans = _call(0.0, 7) + [
        # Another thread's span at the same time is nobody's child.
        Span("hvd.step", 2.0, 4.0, 2, 0)]
    rows = {(s.name, s.thread): (parent and parent.name, own)
            for s, parent, own in trace_regions.nest(reversed(spans))}
    assert rows[("dispatch", 1)] == (None, pytest.approx(1.0))
    assert rows[("hvd.step", 1)] == ("dispatch", pytest.approx(1.5))
    assert rows[("hvd.wrap_step.prepare", 1)] == ("hvd.step",
                                                  pytest.approx(2.0))
    assert rows[("hvd.wrap_step.call", 1)] == ("hvd.step",
                                               pytest.approx(6.5))
    assert rows[("hvd.step", 2)] == (None, pytest.approx(2.0))


def test_a_gap_goes_to_the_innermost_program_span_covering_most_of_it():
    spans = _call(0.0, 0)
    pick = trace_regions.innermost_covering
    assert pick((4.0, 8.0), spans) == "hvd.wrap_step.call"
    # `prepare` covers two thirds of the first gap and a third of the
    # second, the whole call all of both.
    assert pick((0.0, 3.0), spans) == "hvd.wrap_step.prepare"
    assert pick((0.0, 6.0), spans) == "hvd.step"
    # The loop's own span is not the program's; nothing covers this one.
    assert pick((10.2, 10.4), spans) == "none"
    assert pick((20.0, 21.0), spans) == "none"


def _synthetic(every: int = 2, steps: int = 5) -> RegionTrace:
    starts = [10.0 * i for i in range(steps)]
    return RegionTrace(
        ops=tuple(op for s in starts for op in _ops(s)),
        programs=tuple(Event("jit_local_fn(3)", s, s + 8.0) for s in starts),
        # The host runs one step ahead of the device; a call is 10 units.
        spans=tuple(sp for i, s in enumerate(starts)
                    for sp in _call(s - 10.0, i)))


def test_reduce_takes_trace_reduces_window_and_reads_the_spans_in_it():
    regions = trace_regions.reduce(_synthetic(), every=2)
    assert regions.window == (0.0, 40.0) and regions.partition.steps == 4
    assert regions.partition.busy_s == pytest.approx(4 * 8.0)
    # Calls 1..4 start inside the window (call 0 started at -10).
    assert regions.steps_numbered == (1, 2, 3, 4)
    assert regions.span_ms == pytest.approx({
        "hvd.step": 10e3, "hvd.wrap_step.prepare": 2e3,
        "hvd.wrap_step.call": 6.5e3})
    assert regions.span_self_ms["hvd.step"] == pytest.approx(1.5e3)
    assert regions.enclosed == {
        "hvd_step": 4, "inside_one_dispatch": 4,
        "hvd_step_median_ms": pytest.approx(10e3),
        "dispatch_median_ms": pytest.approx(11e3)}
    # Each step leaves 8..10 idle; the call of the next step but one is
    # dispatching then, in its `hvd.wrap_step.call` (3..9.5 of a call).
    assert len(regions.idle_gaps) == 4
    assert {(loop, prog) for loop, prog, _ in regions.idle_gaps} == {
        ("dispatch", "hvd.wrap_step.call")}
    assert all(s == pytest.approx(2.0) for *_, s in regions.idle_gaps)
    metrics = regions.metrics()
    assert list(metrics) == list(trace_regions.METRICS)
    assert metrics["optimizer_ms_per_step"] == pytest.approx(1e3)
    assert metrics["unscoped_device_ms_per_step"] == pytest.approx(0.5e3)
    assert metrics["step_call_host_ms"] == pytest.approx(10e3)
    assert metrics["wrap_step_prepare_host_ms"] == pytest.approx(2e3)
    assert sum(v for k, v in metrics.items() if k.endswith("_per_step")) == (
        pytest.approx(regions.partition.busy_s / 4 * 1e3))
    json.dumps(regions.info())


def test_a_trace_without_the_programs_spans_leaves_their_metrics_out():
    trace = _synthetic()
    loop_only = RegionTrace(trace.ops, trace.programs, tuple(
        s for s in trace.spans if s.name == "dispatch"))
    regions = trace_regions.reduce(loop_only, every=2)
    assert regions.span_ms == {} and regions.enclosed is None
    assert regions.metrics()["step_call_host_ms"] is None
    assert regions.metrics()["wrap_step_prepare_host_ms"] is None
    assert {prog for _, prog, _ in regions.idle_gaps} == {"none"}


# ---------------------------------------------------- the recorded traces

@pytest.fixture(scope="module")
def recorded(tmp_path_factory) -> dict:
    """which -> (the path, this reader's trace, `trace_reduce`'s)."""
    out = {}
    for which, packed in RECORDED.items():
        assert packed.stat().st_size < 500_000
        path = tmp_path_factory.mktemp(which) / packed.name[:-len(".gz")]
        path.write_bytes(gzip.decompress(packed.read_bytes()))
        out[which] = (path, trace_regions.load(str(path)),
                      trace_reduce.load(str(path), harness.SPANS))
    return out


@pytest.mark.parametrize("which,program,ops", [
    ("before", "jit_train_step", 5970),
    ("gspmd", "jit_train_step", 5970),
    ("hvd", "jit_local_fn", 6030),
])
def test_the_loader_reads_what_profile_data_reads(recorded, which, program,
                                                  ops):
    _, mine, theirs = recorded[which]
    chip0 = theirs.devices[0]
    assert len(mine.ops) == len(chip0.ops) == ops
    assert len(mine.programs) == len(chip0.programs) == 15
    assert {trace_reduce.family(p.name).split("(")[0]
            for p in mine.programs} == {program}
    # The same events: `ProfileData` cuts picoseconds to nanoseconds.
    for a, b in zip(mine.ops, chip0.ops):
        assert a.name == b.name
        assert a.start == pytest.approx(b.start, abs=1.1e-9)
        assert a.end == pytest.approx(b.end, abs=2.1e-9)
    assert [p.name for p in mine.programs] == [p.name
                                               for p in chip0.programs]
    loop = sorted((s.name, s.start) for s in mine.spans
                  if s.name in harness.SPANS)
    assert [n for n, _ in loop] == [
        ev.name for ev in sorted(theirs.host_spans,
                                 key=lambda ev: (ev.name, ev.start))]


def test_the_recorded_trace_from_before_the_scopes_pins_its_answer(recorded):
    _, trace, theirs = recorded["before"]
    stacks = {op.tf_op for op in trace.ops}
    assert any(s.startswith(f"{MODEL}/stack/layer_0/") for s in stacks)
    assert any(s.startswith(f"{BACK}/stack/layer_1/") for s in stacks)
    assert {op.category for op in trace.ops} >= {
        "convolution fusion", "loop fusion", "custom-call"}
    assert not any(trace_regions.PROGRAM_PREFIX in s for s in stacks)
    regions = trace_regions.reduce(trace, every=5)
    tables = trace_reduce.reduce(theirs, 5)
    assert regions.partition.steps == tables.steps == 10
    # `ProfileData` cuts every start and duration to whole nanoseconds,
    # a quarter of a percent of this step's microsecond ops (nothing of
    # a cell's); against its own picoseconds the reader checks to 0.1%.
    assert regions.partition.busy_s == pytest.approx(tables.busy_s, rel=5e-3)
    assert sum(regions.partition.seconds.values()) == pytest.approx(
        regions.partition.busy_s, rel=1e-6)
    share = {r: s / regions.partition.busy_s
             for r, s in regions.partition.seconds.items()}
    # The program named neither its loss nor its optimizer then: the
    # loss (`jvp(jit(log_softmax))`) reads as forward, AdamW
    # (`jit(train_step)/add`) and the copies as unscoped.
    assert share == pytest.approx(
        {"forward": 0.298, "backward": 0.476, "loss_head": 0.046,
         "optimizer": 0.0, "unscoped": 0.179}, abs=0.002)
    assert regions.partition.unscoped[0][0] == "jit(train_step)/add"
    assert regions.partition.unscoped[1][0] == "(no tf_op) copy-done"
    # No span of the program's; the loop's spans still name the gaps.
    assert regions.span_ms == {} and regions.steps_numbered == ()
    assert [loop for loop, *_ in regions.idle_gaps] == [
        name for name, _ in tables.idle_gaps]
    assert [s for *_, s in regions.idle_gaps] == pytest.approx(
        [s for _, s in tables.idle_gaps], rel=1e-6)


def test_the_gspmd_trace_holds_both_scopes_and_numbered_step_spans(recorded):
    _, trace, theirs = recorded["gspmd"]
    stacks = {op.tf_op for op in trace.ops}
    assert "jit(train_step)/jvp(hvd.loss)/reduce_sum:" in stacks
    assert any(s.startswith("jit(train_step)/transpose(jvp(hvd.loss))/")
               for s in stacks)
    assert any(s.startswith("jit(train_step)/hvd.optimizer/")
               for s in stacks)
    regions = trace_regions.reduce(trace, every=5)
    busy = regions.partition.busy_s
    assert busy == pytest.approx(trace_reduce.reduce(theirs, 5).busy_s,
                                 rel=5e-3)
    share = {r: s / busy for r, s in regions.partition.seconds.items()}
    # The same program as "before", now named: the loss left `forward`
    # for `loss_head`, AdamW left `unscoped`, which keeps the copies.
    assert share == pytest.approx(
        {"forward": 0.266, "backward": 0.465, "loss_head": 0.089,
         "optimizer": 0.089, "unscoped": 0.091}, abs=0.003)
    assert all(prefix.startswith("(no tf_op) ")
               for prefix, _ in regions.partition.unscoped)
    # Ten calls start in the window, numbered on; each inside one of the
    # loop's `dispatch` spans and a little shorter than it.
    numbers = regions.steps_numbered
    assert len(numbers) == 10
    assert list(numbers) == list(range(numbers[0], numbers[0] + 10))
    assert set(regions.span_ms) == {trace_regions.SPAN_STEP}
    inside = regions.enclosed
    assert inside["hvd_step"] == inside["inside_one_dispatch"] == 10
    assert 0 < (inside["dispatch_median_ms"]
                - inside["hvd_step_median_ms"]) < 0.5
    metrics = regions.metrics()
    assert metrics["wrap_step_prepare_host_ms"] is None
    assert metrics["step_call_host_ms"] == pytest.approx(0.69, abs=0.01)
    assert metrics["optimizer_ms_per_step"] == pytest.approx(0.0069,
                                                             rel=0.02)
    # The gaps are the host's, between calls: no step call is open.
    assert {prog for _, prog, _ in regions.idle_gaps} == {"none"}


def test_the_hvd_trace_holds_wrap_steps_spans_one_thread_nested(recorded):
    _, trace, _ = recorded["hvd"]
    program = [s for s in trace.spans
               if s.name.startswith(trace_regions.PROGRAM_PREFIX)]
    assert len({s.thread for s in trace.spans}) == 1
    by_name = {}
    for span, parent, own in trace_regions.nest(program):
        by_name.setdefault(span.name, []).append((span, parent, own))
    # 15 traced calls, none of them a cache miss.
    assert {n: len(v) for n, v in by_name.items()} == {
        trace_regions.SPAN_STEP: 15, trace_regions.SPAN_WRAP_PREPARE: 15,
        trace_regions.SPAN_WRAP_CALL: 15}
    assert all(parent is None for _, parent, _ in
               by_name[trace_regions.SPAN_STEP])
    for name in (trace_regions.SPAN_WRAP_PREPARE,
                 trace_regions.SPAN_WRAP_CALL):
        assert all(parent.name == trace_regions.SPAN_STEP
                   for _, parent, _ in by_name[name])
    steps = [span.step for span, *_ in by_name[trace_regions.SPAN_STEP]]
    assert steps == list(range(steps[0], steps[0] + 15)) and steps[0] > 0
    assert all(span.step is None for span, *_ in
               by_name[trace_regions.SPAN_WRAP_CALL])

    regions = trace_regions.reduce(trace, every=5)
    ms = regions.span_ms
    # The two parts are the whole call: their medians add up to its
    # median within 0.2 ms (its self time is microseconds).
    assert (ms[trace_regions.SPAN_WRAP_PREPARE]
            + ms[trace_regions.SPAN_WRAP_CALL]) == pytest.approx(
        ms[trace_regions.SPAN_STEP], abs=0.2)
    assert regions.span_self_ms[trace_regions.SPAN_STEP] < 0.02
    assert regions.metrics()["wrap_step_prepare_host_ms"] == pytest.approx(
        0.59, abs=0.01)
    # The device waits for the host in this cell, inside the call.
    assert {loop for loop, *_ in regions.idle_gaps} == {"dispatch"}
    assert {prog for _, prog, _ in regions.idle_gaps} <= {
        trace_regions.SPAN_STEP, trace_regions.SPAN_WRAP_CALL}
    # XLA fused the whole AdamW pass under the root of the user's own
    # `optax.apply_updates` (`add`), which no scope names: the inner
    # update's `hvd.optimizer` reaches no op of this program, and the
    # goodput marker's host callback is most of the device's busy time.
    stacks = {op.tf_op for op in trace.ops}
    assert any("jvp(hvd.loss)" in s for s in stacks)
    assert not any(trace_regions.SCOPE_OPTIMIZER in s for s in stacks)
    assert regions.metrics()["optimizer_ms_per_step"] == 0.0
    assert [p for p, _ in regions.partition.unscoped[:2]] == [
        "jit(local_fn)/debug_callback", "jit(local_fn)/add"]


def test_the_command_prints_the_partition(recorded):
    path, *_ = recorded["before"]
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.trace_regions", str(path), "5"],
        cwd=ROOT, text=True, capture_output=True, check=True).stdout
    assert out.startswith("info: ")
    info = json.loads(out[len("info: "):])
    assert info["steps"] == 10
    assert set(info["regions_ms_per_step"]) == set(trace_regions.REGIONS)
    assert sum(info["regions_ms_per_step"].values()) == pytest.approx(
        info["busy_ms_per_step"], rel=1e-3)
    assert len(info["idle_gaps"]) == 5


def test_a_file_without_a_device_plane_is_an_error(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_regions.load(str(empty))
