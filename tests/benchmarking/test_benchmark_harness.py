"""The benchmark's harness on the CPU at a tiny size: the same
`run_cell` the command calls, driven by the cell files under
tests/benchmarking/cells/ (a two-layer GPT-2 of width 128), for both
trainers and for the two-phase dp=4 path on four virtual devices.
Nothing here is a measurement: times from these runs are never looked
at, only that every declared metric comes out and the result object is
the contract's.
"""
import dataclasses
import functools
import json
import math
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, flops, harness, layer_metrics, peaks
from benchmark.reference import transformer_ref
from benchmark.trace_reduce import DeviceTrace, Event, Trace
from benchmark.trace_regions import Op, RegionTrace, Span
from benchmark.trainers import lm_objective
from horovod_tpu.parallel.train import lm_loss

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = pathlib.Path(__file__).parent / "cells"
INDEX = CELLS / "cells.json"
V5E = peaks.PEAKS["TPU v5 lite"]


def _chip_trace(chips: int, steps: int = 16) -> Trace:
    """What a chip's trace of `steps` steps would hold, made up: the CPU
    has no device plane, so the traced path gets this in place of the
    file it wrote."""
    ops, programs = [], []
    for i in range(steps):
        s = i * 0.010
        programs.append(Event("jit_train_step(1)", s, s + 0.009))
        ops += [Event("fusion.1", s, s + 0.003),
                Event("flash_attention_fwd", s + 0.003, s + 0.004),
                Event("flash_attention_bwd", s + 0.004, s + 0.006),
                Event("all-reduce-start.1", s + 0.006, s + 0.0061),
                Event("fusion.2", s + 0.0061, s + 0.007),
                Event("all-reduce-done.1", s + 0.007, s + 0.009)]
    device = DeviceTrace(ops=tuple(ops), programs=tuple(programs))
    spans = tuple(Event("log_fetch", i * 0.010 + 0.009, (i + 1) * 0.010)
                  for i in range(steps))
    return Trace(devices={i: device for i in range(chips)}, host_spans=spans)


def _chip_regions(steps: int = 16) -> RegionTrace:
    """Chip 0 of `_chip_trace` as `trace_regions.load` would give it: the
    same ops with name stacks as the program's scopes shape them, and
    the program's host spans. A step: forward 4, backward 2, loss and
    head 2, optimizer 0.9, unscoped 0.1 ms."""
    stacks = ["jit(train_step)/jvp(TransformerLM)/stack/layer_0/mlp/wi/dot:",
              "jit(train_step)/jvp(TransformerLM)/stack/layer_0/attn/call:",
              "jit(train_step)/transpose(jvp(TransformerLM))/stack/attn/call:",
              "", "jit(train_step)/hvd.optimizer/mul:",
              "jit(train_step)/transpose(jvp(TransformerLM))/hvd.loss/sub:"]
    device = _chip_trace(1, steps).devices[0]
    ops = tuple(Op(ev.name, ev.start, ev.end, stacks[i % 6], "")
                for i, ev in enumerate(device.ops))
    spans = []
    for i in range(steps):
        s = i * 0.010
        spans += [Span("dispatch", s, s + 0.0015, 1, None),
                  Span("hvd.step", s + 0.0001, s + 0.0011, 1, i),
                  Span("hvd.wrap_step.prepare", s + 0.0002, s + 0.0004, 1,
                       None)]
    return RegionTrace(ops=ops, programs=device.programs, spans=tuple(spans))


def _run(index, name, trace, monkeypatch, seconds=4.0):
    """Four seconds hold several intervals of the tiny step even when
    the suite's other workers load every core (a traced run needs one
    interval before the middle of its main phase)."""
    chips = harness.load_cell(index, name).chips
    monkeypatch.setattr(harness.trace_reduce, "load",
                        lambda path, spans: _chip_trace(chips))
    monkeypatch.setattr(harness.trace_regions, "load",
                        lambda path: _chip_regions())
    # The chip's cells check the loss after 20 steps; how many steps a
    # loaded CPU manages in a short window is not this test's business.
    monkeypatch.setattr(harness, "LOSS_AT_STEP", 5)
    return harness.run_cell(index, name, seed=3, seconds=seconds, trace=trace,
                            devices=jax.devices(), peaks=V5E,
                            t0=time.perf_counter())


def _check_contract(result, declared, trace):
    assert set(result) == ({"correct", "attempted", "failed", "metrics",
                            "device", "compared"}
                           | ({"breakdown"} if trace else set()))
    assert set(result["metrics"]) == set(declared)
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == declared[name]
        assert math.isfinite(metric["value"]), name
    device = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert (device["platform"], device["count"]) == ("cpu", 8)
    assert set(device["versions"]) == {"jax", "jaxlib", "libtpu"}
    assert result["failed"] == 0 and result["attempted"] > 0
    # The only check a CPU run must fail is the one for the platform.
    assert result["correct"] is False
    json.dumps(result)
    if trace:
        assert device["busy_s"] > 0 and device["window_s"] > device["busy_s"]
        ops = result["breakdown"]["device_ops"]
        gaps = result["breakdown"]["idle_gaps"]
        assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
        assert {name for name, _ in gaps} <= set(harness.SPANS) | {"none"}


@pytest.mark.parametrize("name,trace", [
    ("tiny-gspmd-1c", False),
    ("tiny-hvd-1c", True),
    ("tiny-gspmd-dp4", False),
    ("tiny-gspmd-dp4", True),
])
def test_tiny_cell_yields_every_declared_metric(name, trace, monkeypatch,
                                                capsys):
    cell = harness.load_cell(INDEX, name)
    result = _run(INDEX, name, trace, monkeypatch)
    _check_contract(result, cell.per_layer if trace else cell.end_to_end,
                    trace)
    info = [json.loads(line[len("info: "):])
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("info: {")]
    checks = next(i for i in info if "checks" in i)
    failed = {k for k, ok in checks["checks"].items() if not ok}
    assert failed == {"platform_is_tpu"}
    assert checks["compiles_in_window"] == 0
    if cell.chips == 4:
        assert len(checks["checksums"]) == 4
        assert len(set(checks["checksums"])) == 1
        phases = {i["phase"]: i for i in info if "phase" in i}
        assert set(phases) == {"baseline", "main"}
        assert phases["baseline"]["intervals"] >= 1
        if trace:
            assert "scaling_efficiency" not in result["metrics"]
            assert "dp1_tokens_per_s_per_chip" in result["metrics"]
        else:
            assert "scaling_efficiency" in result["metrics"]


def _with_step(monkeypatch, broken_step):
    """The gspmd trainer with `broken_step(real_step, state, batch)` in
    its step's place: the timed path broken underneath `run_cell`."""
    from benchmark.trainers import gspmd

    real = gspmd.build

    def build(model, phase, devices, seed):
        trainer = real(model, phase, devices, seed)
        return dataclasses.replace(trainer, step=functools.partial(
            broken_step, trainer.step))

    monkeypatch.setattr(gspmd, "build", build)


def _state_unchanged(step, state, batch):
    # The real step donates its state: it gets a copy.
    _, loss = step(jax.tree.map(jnp.copy, state), batch)
    return state, loss


def _one_module_frozen(step, state, batch):
    new, loss = step(jax.tree.map(jnp.copy, state), batch)
    new.params = dict(new.params, ln_f=state.params["ln_f"])
    return new, loss


@pytest.mark.parametrize("broken_step,unmoved", [
    (None, 0), (_state_unchanged, "all"), (_one_module_frozen, 2)])
def test_a_step_that_leaves_parameters_unmoved_is_not_correct(
        broken_step, unmoved, monkeypatch, capsys):
    """`run_cell` with the step broken underneath: a step that returns
    its state unchanged, or never updates one module (the final
    LayerNorm's scale and bias), passes every other check (the loss
    after a few steps at 1e-4 lies in any band that holds the initial
    loss) and fails `state_moves`. Every number `correct` rests on
    comes beside its limit, last in the result."""
    if broken_step is not None:
        _with_step(monkeypatch, broken_step)
    result = _run(INDEX, "tiny-gspmd-1c", False, monkeypatch, seconds=2.0)
    checks = next(json.loads(line[len("info: "):])["checks"]
                  for line in capsys.readouterr().out.splitlines()
                  if line.startswith('info: {"checks"'))
    failed = {k for k, ok in checks.items() if not ok}
    assert failed == {"platform_is_tpu"} | (
        {"state_moves"} if broken_step else set())
    assert result["correct"] is False

    compared = result["compared"]
    assert list(result)[-1] == "compared"
    assert all(set(row) == {"value", "limit"} for row in compared.values())
    assert {"logits", "grad_norm", "leaves_unmoved", "loss_after_20",
            "compiles_in_window", "losses_not_finite"} <= set(compared)
    assert compared["logits"]["limit"] == correct.LOGITS_TOL
    assert compared["grad_norm"]["limit"] == correct.GRAD_TOL
    assert compared["leaves_unmoved"]["limit"] == 0
    cell = harness.load_cell(INDEX, "tiny-gspmd-1c")
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: harness.make_model(cell).init(
            jax.random.PRNGKey(0), np.zeros((1, 256), np.int32)))))
    assert compared["leaves_unmoved"]["value"] == (
        leaves if unmoved == "all" else unmoved)


def test_files_added_in_a_copy_are_found_by_name(tmp_path, monkeypatch):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric as new files plus entries in BENCHMARK.json, and
    edits nothing that is there."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = tmp_path / "benchmark"
    shutil.copy(CELLS / "configs" / "gpt2-tiny.json", added / "configs")
    (added / "traffic" / "s128-b2-gspmd.json").write_text(json.dumps(
        {"seq": 128, "batch_per_chip": 2, "trainer": "gspmd",
         "mesh": {"dp": 1}, "log_every": 2, "pool": 3}))
    (added / "workloads" / "tiny-added.json").write_text(json.dumps(
        {"loss_after_20": 6.9, "loss_band": 0.3}))
    (added / "layer_metrics" / "traced_steps.py").write_text(
        '"""Steps in the traced window."""\n\n\n'
        "def compute(ctx):\n    return ctx.tables.steps\n")
    index = json.loads((ROOT / "BENCHMARK.json").read_text())
    index["configs"].append(
        {"name": "gpt2-tiny", "source": "toy", "reduced": [], "why": "test",
         "file": "benchmark/configs/gpt2-tiny.json"})
    index["workloads"].append(
        {"name": "tiny-added", "config": "gpt2-tiny", "chips": 1,
         "traffic": "s128-b2-gspmd", "why": "test"})
    index["per_layer"].append(
        {"name": "traced_steps", "unit": "steps", "better": "higher",
         "source": "device_trace", "layer": "device",
         "moves": "tokens_per_s_per_chip", "workloads": ["tiny-added"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(index))
    # The package's directory of readers, as the copy's checkout has it.
    monkeypatch.setattr(layer_metrics, "__path__",
                        [str(added / "layer_metrics")])

    cell = harness.load_cell(tmp_path / "BENCHMARK.json", "tiny-added")
    assert (cell.traffic["seq"], cell.dims["d_model"]) == (128, 128)
    result = _run(tmp_path / "BENCHMARK.json", "tiny-added", True,
                  monkeypatch)
    # 16 made-up starts, a fetch every 2 steps: 14 whole steps traced.
    assert result["metrics"]["traced_steps"] == {"value": 14,
                                                 "unit": "steps"}
    # The metrics listed for other cells only are not this cell's.
    assert "flash_attn_ms_per_step" not in result["metrics"]
    assert "device_step_ms" in result["metrics"]


def test_index_names_only_files_that_exist():
    index = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert index["paths"] == ["benchmark", "tests/benchmarking"]
    for entry in index["workloads"]:
        cell = harness.load_cell(ROOT / "BENCHMARK.json", entry["name"])
        assert math.prod(cell.phases[-1]["mesh"].values()) == entry["chips"]
        assert (ROOT / "benchmark" / "trainers"
                / f"{cell.traffic['trainer']}.py").exists()
        for metric in cell.per_layer:
            assert (ROOT / "benchmark" / "layer_metrics"
                    / f"{metric}.py").exists(), metric
    for config in index["configs"]:
        held = json.loads((ROOT / config["file"]).read_text())
        assert held["source"] == config["source"]
        assert held["reduced"] == config["reduced"]


@pytest.mark.parametrize("path", [
    *sorted((ROOT / "benchmark" / "configs").glob("*.json")),
    *sorted((CELLS / "configs").glob("*.json"))], ids=lambda p: p.stem)
def test_a_transformer_configuration_states_the_kernel_work_its_dims_give(
        path):
    """`attention` and `kernels` restate what the dims of a model of
    `reference/transformer_ref.py`'s block fix: every layer calls the
    attention once, on `n_heads` heads of `d_model // n_heads` for
    queries, keys and values alike, and the step holds the flash kernels
    exactly where `attn_impl` asks for them. A depth cut that forgets
    `calls_per_step` would halve a roofline share unseen."""
    from benchmark.layer_metrics import _flash

    held = json.loads(path.read_text())
    assert held["reference"] == "transformer_ref"
    dims = {kw: held[key] for kw, key in held["model_kwargs"].items()}
    dims.update(held["model_options"])
    head = dims["d_model"] // dims["n_heads"]
    assert held["attention"] == {
        "heads": dims["n_heads"], "qk_head_dim": head, "v_head_dim": head,
        "calls_per_step": dims["n_layers"]}
    assert held["kernels"] == ([_flash.FORWARD, _flash.BACKWARD]
                               if dims["attn_impl"] == "flash" else [])
    assert held["matmul_params"] == "flops.transformer_matmul_params"


def test_the_command_refuses_the_cpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2s-s4096-gspmd-1c", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "JAX_COMPILATION_CACHE_DIR": "/nonexistent"})
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert proc.stdout == ""


def test_unknown_device_kind_is_an_error():
    class Device:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    with pytest.raises(peaks.UnknownDevice, match="not in"):
        peaks.for_device(Device)
    assert peaks.for_device(
        type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})
    ).bf16_flops_per_s == 197e12


@pytest.mark.parametrize("config,seq,mflop", [
    ("gpt2-small", 2048, 854), ("gpt2-small", 4096, 968),
    ("bert-base", 128, 664)])
def test_flops_per_token_equals_the_hand_count(config, seq, mflop):
    """By hand, gpt2-small: per block 4 d^2 + 2 d d_ff = 7,077,888
    matmul parameters, 12 blocks, the head 768 x 50257: 123,532,032, so
    247.06 MFLOP a token forward; causal attention 2 S d a block:
    37.75 MFLOP at 2048, 75.50 at 4096; times 3 for the step: 854.4 and
    967.7. bert-base: 108,375,552 parameters, 216.75 MFLOP, attention
    4 S d a block = 4.72: 664.4."""
    held = json.loads((ROOT / "benchmark" / "configs"
                       / f"{config}.json").read_text())
    dims = {kw: held[key] for kw, key in held["model_kwargs"].items()}
    dims.update(held["model_options"])
    got = flops.transformer(dims, seq)
    assert round(got / 1e6) == mflop
    d, f, v, n = 768, 3072, dims["vocab_size"], 12
    attention = (2 if dims["causal"] else 4) * seq * d * n
    assert got == 3 * (2 * (n * (4 * d * d + 2 * d * f) + d * v) + attention)


def test_matmul_flops_agree_with_xlas_count_of_the_flash_program():
    """Lowered for the TPU the flash kernel is a custom call XLA cannot
    see into, so its count of the tiny model's forward + backward is the
    matmuls plus the elementwise ops (about 2% at this width; 0.6% at
    gpt2-small's, where the v5e compile test makes the same check)."""
    cell = harness.load_cell(INDEX, "tiny-gspmd-1c")
    model = harness.make_model(cell)
    ids = np.zeros((2, 256), np.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    lowered = jax.jit(jax.grad(
        lambda p: lm_loss(model.apply(p, ids), ids))).trace(params).lower(
            lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    matmul = 3 * 2 * flops.transformer_matmul_params(cell.dims) * ids.size
    assert lowered.cost_analysis()["flops"] == pytest.approx(matmul, rel=0.03)
    assert lowered.cost_analysis()["flops"] > matmul


@pytest.fixture(scope="module")
def tiny():
    cell = harness.load_cell(INDEX, "tiny-gspmd-1c")
    model = harness.make_model(cell)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 np.zeros((1, 256), np.int32))["params"]
    params = jax.tree.map(lambda x: x.value if hasattr(x, "value") else x,
                          params, is_leaf=lambda x: hasattr(x, "value"))
    return cell, lm_objective(model, lm_loss), params


def test_reference_agrees_with_the_program_on_the_tiny_model(tiny):
    cell, objective, params = tiny
    errors = correct.measure_against_reference(
        objective, transformer_ref, params, cell.dims, 256, seed=1,
        grad_leaves=cell.config["grad_leaves"])
    assert set(errors) == {"logits", "grad_norm", "grad.embedding",
                           "grad.layer_1.qkv", "grad.ln_f.scale"}
    assert correct.beyond_tolerance(errors) == {}


@pytest.mark.parametrize("broken", [{"causal": False}, {"n_layers": 1}])
def test_reference_comparison_fails_without_the_mask_or_a_layer(tiny, broken):
    cell, objective, params = tiny
    errors = correct.measure_against_reference(
        objective, transformer_ref, params, dict(cell.dims, **broken), 256,
        seed=1, grad_leaves=cell.config["grad_leaves"])
    assert "logits" in correct.beyond_tolerance(errors)
    assert errors["logits"] > 5 * correct.LOGITS_TOL


def test_loss_band_and_nan():
    assert correct.loss_in_band(10.8, 10.9, 0.2)
    assert not correct.loss_in_band(10.5, 10.9, 0.2)
    assert not correct.loss_in_band(math.nan, 10.9, 0.2)
    assert correct.beyond_tolerance({"logits": math.nan}) != {}
