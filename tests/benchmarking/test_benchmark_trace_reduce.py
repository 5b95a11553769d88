"""The reduction from a profiler trace to the per-layer tables
(benchmark/trace_reduce.py): on a synthetic event list whose answers
are known by construction, and on a small trace recorded on the chip
(TPU v5e, a tiny cell of tests/benchmarking/cells/, PR 22)."""
import gzip
import pathlib

import pytest

from benchmark import harness, trace_reduce
from benchmark.trace_reduce import DeviceTrace, Event, Trace

RECORDED = (pathlib.Path(__file__).parent / "data"
            / "tiny_step.xplane.pb.gz")


def _step(s: float) -> list:
    """One synthetic step of 10 time units starting at `s`: 8 busy, the
    all-reduce in flight from 4 to 8 with compute under it until 6.5."""
    return [
        Event("fusion.1", s + 0.0, s + 3.0),
        Event("flash_attention_fwd", s + 3.0, s + 5.0),
        Event("all-reduce-start.1", s + 4.0, s + 4.5),
        Event("fusion.2", s + 5.0, s + 6.5),
        Event("all-reduce-done.1", s + 6.0, s + 8.0),
    ]


@pytest.fixture
def synthetic() -> Trace:
    starts = [0.0, 10.0, 20.0, 30.0, 40.0, 50.0]
    programs = tuple(Event("jit_train_step(7)", s, s + 8.0) for s in starts)
    # A short program of another name must not be taken for the step.
    programs += (Event("jit_convert(3)", 8.5, 8.6),)
    chip0 = DeviceTrace(
        ops=tuple(e for s in starts for e in _step(s)), programs=programs,
        # As the chip reports an async op: once, start to end of done,
        # under the start's instruction.
        async_ops=tuple(Event("%all-reduce-start.1 = f32[8] all-reduce-start("
                              "f32[8] %p)", s + 4.0, s + 8.0) for s in starts)
        + tuple(Event("%copy-start.3 = f32[8] copy-start(f32[8] %q)",
                      s + 0.0, s + 9.0) for s in starts))
    # Chip 1 is busy only half as long.
    chip1 = DeviceTrace(ops=tuple(Event("fusion.1", s, s + 4.0)
                                  for s in starts), programs=programs)
    spans = (
        Event("log_fetch", 8.2, 9.9),      # covers the gap after step 1
        Event("dispatch", 18.0, 18.4),     # gap after step 2: mostly feed
        Event("feed", 18.4, 19.9),
        Event("log_fetch", 38.5, 40.5),    # nothing covers 28..30
    )
    return Trace(devices={0: chip0, 1: chip1}, host_spans=spans)


def test_window_is_whole_logging_intervals(synthetic):
    # Six starts hold five periods; with a fetch every 2 steps the window
    # is the first 4.
    tables = trace_reduce.reduce(synthetic, every=2)
    assert tables.steps == 4
    assert tables.window_s == pytest.approx(40.0)
    assert tables.step_period_s == pytest.approx(10.0)
    with pytest.raises(ValueError, match="fewer than"):
        trace_reduce.reduce(synthetic, every=6)


def test_busy_is_a_union_and_idle_is_the_rest(synthetic):
    tables = trace_reduce.reduce(synthetic, every=2)
    # Ops overlap (the all-reduce halves lie over compute): 9 units of
    # durations a step, 8 of them distinct.
    assert sum(tables.op_seconds.values()) == pytest.approx(4 * 9.0)
    assert tables.busy_s == pytest.approx(4 * 8.0)
    assert tables.busy_s_mean == pytest.approx((4 * 8.0 + 4 * 4.0) / 2)
    assert 1 - tables.busy_s / tables.window_s == pytest.approx(0.2)


def test_kernel_sums_go_by_name(synthetic):
    tables = trace_reduce.reduce(synthetic, every=2)
    assert tables.op_seconds["flash_attention_fwd"] == pytest.approx(4 * 2.0)
    assert tables.op_seconds["fusion"] == pytest.approx(4 * 4.5)
    assert tables.seconds_of("flash_attention_fwd",
                             "flash_attention_bwd") == pytest.approx(8.0)
    assert tables.seconds_of("flash_attention_bwd") is None
    assert tables.top_ops(2) == [["fusion", pytest.approx(18.0)],
                                 ["flash_attention_fwd", pytest.approx(8.0)]]
    # Single ops keep their own names: fusion.1 is the heaviest one.
    assert tables.heaviest[0] == ("fusion.1", pytest.approx(12.0))


def test_exposed_collective_time_is_what_no_compute_covers(synthetic):
    tables = trace_reduce.reduce(synthetic, every=2)
    # In flight from start.begin to done.end = 4 a step; compute runs
    # under it until 6.5, so 1.5 is exposed.
    assert tables.collective_s == pytest.approx(4 * 4.0)
    assert tables.collective_exposed_s == pytest.approx(4 * 1.5)


def test_idle_gaps_are_attributed_to_the_covering_host_span(synthetic):
    tables = trace_reduce.reduce(synthetic, every=2)
    assert [name for name, _ in tables.idle_gaps] == [
        "log_fetch", "feed", "none", "log_fetch"]
    assert all(s == pytest.approx(2.0) for _, s in tables.idle_gaps)
    assert len(trace_reduce.reduce(synthetic, every=2, gaps=2).idle_gaps) == 2


def test_interval_arithmetic():
    assert trace_reduce.merge([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [
        (0, 2.5), (3, 4)]
    assert trace_reduce.covered([(0, 2), (1, 3), (10, 11)]) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert trace_reduce.family("fusion.12.3") == "fusion"
    assert trace_reduce.family("jit_train_step(77)") == "jit_train_step(77)"


def test_collectives_by_xla_name_sync_and_async():
    device = DeviceTrace(
        ops=(Event("%all-gather.3 = f32[4] all-gather(f32[1] %x)", 0, 1),
             Event("fusion.1", 0, 9),
             Event("reduce-scatter-start.2", 2, 2.1),
             Event("all-reduce-scatter-fusion", 3, 4),  # not a collective op
             Event("reduce-scatter-done.2", 5, 6)),
        programs=(),
        async_ops=(Event("reduce-scatter-start.2", 2, 6),
                   Event("copy-start.1", 0, 9)))
    assert trace_reduce.collective_intervals(device) == [(0, 1), (2, 6)]
    assert trace_reduce.is_collective("all-reduce-start.11")
    assert trace_reduce.is_collective("%all-reduce-done.2 = f32[8] all-red")
    assert not trace_reduce.is_collective("flash_attention_fwd")
    assert trace_reduce.op_name(
        "%flash_attention_bwd.3 = (bf16[4,256,64]) custom-call(bf16[4] %b)"
    ) == "flash_attention_bwd.3"


# ------------------------------------------------------ the recorded trace

@pytest.fixture(scope="module")
def recorded(tmp_path_factory) -> Trace:
    """`tiny-gspmd-1c` of tests/benchmarking/cells/ on one TPU v5e chip
    (jax 0.9.0, libtpu 0.0.34): the 15 traced steps of a `--trace 1`
    run, as the profiler wrote them, gzipped (1.5 MB raw)."""
    assert RECORDED.stat().st_size < 500_000
    path = tmp_path_factory.mktemp("trace") / "tiny_step.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return trace_reduce.load(str(path), harness.SPANS)


def test_recorded_trace_has_one_chip_its_programs_and_our_spans(recorded):
    assert sorted(recorded.devices) == [0]
    chip = recorded.devices[0]
    assert trace_reduce.step_program(chip.programs).startswith(
        "jit_train_step(")
    assert len(chip.programs) == 15
    assert {ev.name for ev in recorded.host_spans} == set(harness.SPANS)
    # Async copies are in flight there, no collective on one chip.
    assert chip.async_ops and trace_reduce.collective_intervals(chip) == []


def test_recorded_trace_reduces_to_ten_steps_with_the_kernels_by_name(
        recorded):
    tables = trace_reduce.reduce(recorded, every=5)
    assert tables.steps == 10
    # The tiny model has 2 layers: 2 forward and 2 backward kernel calls
    # a step, found under the names ops/flash_attention.py gives them.
    chip = recorded.devices[0]
    starts = trace_reduce.step_starts(chip.programs, 5)
    calls = [trace_reduce.family(ev.name)
             for ev in trace_reduce.clip(chip.ops, (starts[0], starts[-1]))]
    assert calls.count("flash_attention_fwd") == 2 * 10
    assert calls.count("flash_attention_bwd") == 2 * 10
    # As read by hand from the trace: 4.5 and 6.1 microseconds a call.
    assert tables.seconds_of("flash_attention_fwd") == pytest.approx(
        20 * 4.5e-6, rel=0.05)
    assert tables.seconds_of("flash_attention_bwd") == pytest.approx(
        20 * 6.1e-6, rel=0.05)
    # Ops of one core do not overlap: the union equals the sum, and a
    # step this small leaves the chip idle most of the window.
    assert tables.busy_s == pytest.approx(sum(tables.op_seconds.values()),
                                          rel=1e-6)
    assert tables.busy_s_mean == pytest.approx(tables.busy_s)
    assert 0.9 < 1 - tables.busy_s / tables.window_s < 1
    assert tables.collective_s == 0 and tables.collective_exposed_s == 0
    assert tables.step_period_s == pytest.approx(tables.window_s / 10,
                                                 rel=0.2)
    # Every one of the longest gaps is the host's, under one of our spans.
    assert len(tables.idle_gaps) == 5
    assert {name for name, _ in tables.idle_gaps} <= set(harness.SPANS)
    assert tables.idle_gaps[0][0] == "log_fetch"
