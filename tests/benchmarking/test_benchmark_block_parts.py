"""The readers of the parts every model family names the same way
(`benchmark/layer_metrics/_blocks.py`'s vocabulary: `mlp_ms_per_step`,
`mlp_roofline`, `norm_ms_per_step`, `embed_ms_per_step`,
`model_unattributed_ms_per_step`): the vocabulary against the program's,
each metric from a synthetic op list whose answer is known by
construction, the roofline's least time against the hand figures, and a
trace from before the program named these parts (one recorded on the
chip) read as nothing, so that the metrics are left out as on a parent
of the scopes."""
import functools
import gzip
import pathlib
import types

import pytest

from benchmark import flops_blocks, harness, peaks
from benchmark.layer_metrics import (
    _blocks, _scopes, embed_ms_per_step, mlp_ms_per_step, mlp_roofline,
    model_unattributed_ms_per_step, norm_ms_per_step)
from benchmark.trace_reduce import Event
from benchmark.trace_regions import Op, RegionTrace
from horovod_tpu.common import tracing

ROOT = pathlib.Path(__file__).resolve().parents[2]
INDEX = ROOT / "BENCHMARK.json"
BEFORE = pathlib.Path(__file__).parent / "data" / "tiny_gspmd_step.xplane.pb.gz"
V5E = peaks.PEAKS["TPU v5 lite"]
READERS = (mlp_ms_per_step, mlp_roofline, norm_ms_per_step,
           embed_ms_per_step, model_unattributed_ms_per_step)

FWD = "jit(train_step)/jvp(M)/"
BWD = "jit(train_step)/transpose(jvp(M))/"
# One made-up step, ms: (op name, name stack).
STEP = [
    (4.0, "fusion.1", FWD + "stack/layer_0/hvd.mlp/mlp/wi/dot_general:"),
    (6.0, "fusion.2", BWD + "stack/layer_0/hvd.mlp/mlp/wo/dot_general:"),
    (1.0, "fusion.3", FWD + "stack/layer_0/hvd.norm/ln1/reduce_sum:"),
    (0.5, "fusion.4", BWD + "hvd.norm/ln_f/mul:"),
    (0.25, "fusion.5", FWD + "hvd.embed/embed/jit(_take)/gather:"),
    (0.75, "fusion.6", BWD + "hvd.embed/embed/jit(_take)/scatter-add:"),
    (2.0, "fusion.7", FWD + "stack/layer_0/attn/hvd.attn.proj/qkv/dot:"),
    (3.0, "flash_attention_fwd.1", FWD + "stack/layer_0/attn/flash:"),
    (0.4, "add.1", FWD + "stack/layer_0/add:"),
    (0.6, "fusion.8", BWD + "stack/layer_1/add_any:"),
    (2.0, "fusion.9", FWD + "lm_head/dot_general:"),
    (1.0, "fusion.10", "jit(train_step)/hvd.optimizer/adamw:"),
    (0.3, "copy-done.1", ""),
]


def _trace(step=STEP, steps: int = 10) -> RegionTrace:
    """`steps` copies of `step` back to back, 1 ms apart, and a program
    event per step (one more start closes the window)."""
    ops, programs, t = [], [], 0.0
    for _ in range(steps):
        programs.append(Event("jit_train_step(1)", t, t + 0.05))
        for ms, name, stack in step:
            ops.append(Op(name, t, t + ms * 1e-3, stack, ""))
            t += ms * 1e-3
        t += 1e-3
    programs.append(Event("jit_train_step(1)", t, t + 0.05))
    return RegionTrace(ops=tuple(ops), programs=tuple(programs), spans=())


def _ctx(monkeypatch, trace, cell=None):
    monkeypatch.setattr(_scopes, "_load", lambda path: trace)
    cell = cell or types.SimpleNamespace(
        traffic={"log_every": 2}, config={"kernels": ["flash_attention_fwd"]})
    return types.SimpleNamespace(cell=cell, peaks=V5E, trace_file="made-up")


def test_the_readers_vocabulary_is_the_programs():
    assert (_blocks.MLP, _blocks.NORM, _blocks.EMBED) == (
        tracing.SCOPE_MLP, tracing.SCOPE_NORM, tracing.SCOPE_EMBED)


@pytest.mark.parametrize("reader,ms", [
    (mlp_ms_per_step, 10.0),
    (norm_ms_per_step, 1.5),
    (embed_ms_per_step, 1.0),
    # The residual adds: not the scoped ops, not the flash kernel (a
    # `kernels` entry), not the head, the optimizer or an op without a
    # name stack.
    (model_unattributed_ms_per_step, 1.0),
], ids=lambda x: getattr(x, "__name__", x))
def test_each_reader_sums_its_ops_a_step(reader, ms, monkeypatch):
    assert reader.compute(_ctx(monkeypatch, _trace())) == pytest.approx(ms)


def test_unattributed_leaves_out_scoped_ops_and_named_kernels():
    ops = _trace().ops
    read = functools.partial(model_unattributed_ms_per_step.unattributed,
                             window=(0.0, ops[-1].end), steps=10)
    seconds, heaviest = read(ops, kernels=["flash_attention_fwd"])
    assert seconds == pytest.approx(1e-3)
    # Layers folded together, heaviest first.
    assert [(stack, family) for stack, family, _ in heaviest] == [
        (BWD + "stack/layer_*/add_any", "fusion"),
        (FWD + "stack/layer_*/add", "add")]
    # Without the kernel among `kernels`, its 3 ms a step are the
    # model's; any `hvd.` scope keeps an op out, a new one too.
    assert read(ops, kernels=[])[0] == pytest.approx(4e-3)
    renamed = [op._replace(tf_op=op.tf_op.replace("layer_1/", "hvd.x/"))
               for op in ops]
    assert read(renamed, kernels=["flash_attention_fwd"])[0] == (
        pytest.approx(0.4e-3))


@pytest.mark.parametrize("cell,least_ms", [
    # 2 products x 12 layers x 16,384 tokens of 768 x 3072.
    ("gpt2s-s4096-gspmd-1c", 28.25),
    ("gpt2s-s2048-gspmd-dp4", 28.25),
    ("gpt2s-s2048-hvd-1c", 28.25),
    # The same at 32,768 tokens.
    ("bert-base-s128-gspmd-1c", 56.5),
    # One gated layer of 8192 (3 products), 16,384 tokens.
    ("laguna-s8192-b2-1c", 25.1),
    # One gated layer of 7168, 8192 tokens; the module's block is routed.
    ("joyai-s4096-b2-1c", 11.0),
])
def test_the_least_time_is_the_hand_figure(cell, least_ms):
    cell = harness.load_cell(INDEX, cell)
    tokens = cell.traffic["batch_per_chip"] * cell.traffic["seq"]
    rows = flops_blocks.least_seconds(cell.dims, tokens, V5E)
    assert {bound for _, _, bound, _ in rows} == {"compute"}
    assert sum(s for *_, s in rows) * 1e3 == pytest.approx(least_ms,
                                                           rel=0.005)


def test_the_roofline_divides_least_time_by_traced_time(monkeypatch,
                                                         capsys):
    """40 ms a step under `hvd.mlp` in the s4096 cell: 28.26 / 40."""
    step = [(40.0, "fusion.1", FWD + "stack/layer_0/hvd.mlp/mlp/wi/dot:")]
    ctx = _ctx(monkeypatch, _trace(step),
               harness.load_cell(INDEX, "gpt2s-s4096-gspmd-1c"))
    assert mlp_roofline.compute(ctx) == pytest.approx(70.64, rel=1e-3)
    info = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("info: dense feed-forward")]
    assert len(info) == 4 and all("compute-bound" in line for line in info)


def test_the_product_cost_counts_both_gradients():
    fwd = flops_blocks.product_cost(10, 3, 5, backward=False)
    bwd = flops_blocks.product_cost(10, 3, 5, backward=True)
    assert fwd == (300.0, 2.0 * (10 * 8 + 15))
    assert bwd == (600.0, 2 * fwd[1])


def test_a_program_without_the_scopes_gives_nothing(monkeypatch):
    """Made up: the step above with every new name taken out."""
    bare = [(ms, name, stack.replace("hvd.mlp/", "").replace(
        "hvd.norm/", "").replace("hvd.embed/", ""))
        for ms, name, stack in STEP]
    ctx = _ctx(monkeypatch, _trace(bare))
    assert [r.compute(ctx) for r in READERS] == [None] * len(READERS)
    assert model_unattributed_ms_per_step.compute(
        types.SimpleNamespace(trace_file=None)) is None


def test_a_trace_recorded_before_the_scopes_gives_nothing(tmp_path):
    """A chip trace of the tiny gpt2 cell from before the program named
    these parts (PR 25), through the real loader."""
    path = tmp_path / BEFORE.name[:-len(".gz")]
    path.write_bytes(gzip.decompress(BEFORE.read_bytes()))
    cell = harness.load_cell(
        pathlib.Path(__file__).parent / "cells" / "cells.json",
        "tiny-gspmd-1c")
    ctx = types.SimpleNamespace(cell=cell, peaks=V5E,
                                trace_file=str(path))
    assert [r.compute(ctx) for r in READERS] == [None] * len(READERS)
