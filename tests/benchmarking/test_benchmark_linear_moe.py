"""The delta-rule / latent-attention mixture-of-experts configuration
through the benchmark, on the CPU at a tiny size: the cell files under
linear_cells/ (an index of their own; the benchmark's trainer,
reference, FLOP functions and per-layer readers found by name) run
through `run_cell` as `test_benchmark_harness.py` runs its toy; the FLOP
and chunk-cost functions against a hand count; the delta rule's two
readers; the real configuration's published keys, `attention`,
`kernels` and `grad_leaves` against the model it builds.
"""
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (
    correct, flops, flops_linear_moe, flops_moe, harness, peaks)
from benchmark.layer_metrics import _kda, _scopes, kda_ms_per_step, \
    kda_roofline
from benchmark.trace_reduce import Event
from benchmark.trace_regions import Op, RegionTrace
from benchmark.trainers import gspmd
from horovod_tpu.common import tracing
from horovod_tpu.models.linear_moe import LINEAR_MOE_CONFIGS
from horovod_tpu.ops import kda
from test_benchmark_harness import _check_contract, _run

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "linear_cells" / "cells.json"
CELL = "linear-tiny-1c"
INDEX = ROOT / "BENCHMARK.json"
REAL = "kimi-linear-s8192-b2-1c"
# The model's own scopes, which the made-up chip trace of `_run` holds
# none of: their readers find nothing there.
SCOPED = ("attn_proj_ms_per_step", "latent_proj_ms_per_step",
          "moe_route_ms_per_step", "moe_experts_ms_per_step",
          "moe_experts_roofline", "kda_ms_per_step", "kda_roofline")
V5E = peaks.PEAKS["TPU v5 lite"]


def _real():
    return harness.load_cell(INDEX, REAL)


# ------------------------------------------------------- through run_cell

@pytest.mark.parametrize("trace", [False, True])
def test_the_tiny_cell_runs_to_the_contract(trace, monkeypatch, capsys):
    cell = harness.load_cell(TINY, CELL)
    result = _run(TINY, CELL, trace, monkeypatch)
    declared = dict(cell.per_layer if trace else cell.end_to_end)
    if trace:
        for name in SCOPED:
            assert declared.pop(name) in ("ms", "%")
    _check_contract(result, declared, trace)
    info = [json.loads(line[len("info: "):])
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("info: {")]
    checks = next(i for i in info if "checks" in i)["checks"]
    assert {k for k, ok in checks.items() if not ok} == {"platform_is_tpu"}
    compared = result["compared"]
    assert set(compared) == {
        "logits", "choice_slack", "grad_norm", "grad.embedding",
        "grad.layer_1.f_b", "grad.layer_2.kv_b",
        "grad.layer_1.experts.gate_up", "grad.final_norm.scale",
        "leaves_unmoved", "loss_after_20", "compiles_in_window",
        "losses_not_finite"}
    assert 0 <= compared["choice_slack"]["value"] <= correct.CHOICE_TOL
    assert compared["leaves_unmoved"]["value"] == 0


def test_the_tiny_cell_lowers_with_its_kernels():
    """The v5e compile test holds the real cell to its `kernels` at the
    real size; here the tiny cell's lowered step (for the CPU: the
    kernels interpreted) names them, and every scope of the model."""
    cell = harness.load_cell(TINY, CELL)
    lowered = gspmd.lower(harness.make_model(cell), cell.phases[0],
                          jax.devices()[:1])
    text = lowered.as_text(debug_info=True)
    for name in cell.config["kernels"] + [
            tracing.SCOPE_ATTN_PROJ, tracing.SCOPE_ATTN_KDA,
            tracing.SCOPE_ATTN_LATENT, tracing.SCOPE_MOE_ROUTE,
            tracing.SCOPE_MOE_EXPERTS]:
        assert name in text, name


# ------------------------------------------------------------ the counting

def test_flops_of_the_share_equal_the_hand_count():
    """By hand from the published sizes: a delta-rule layer's products
    39.46 M entries (q, k, v 9.44 M each, the two low-rank pairs 0.82 M
    each, beta 0.07 M, o 9.44 M), the latent layer's 29.11 M (q 14.16,
    kv_a 1.33, kv_b 4.19, o 9.44), the dense feed-forward 63.70 M, a
    router 0.59 M, the shared expert and one expert 7.08 M each, the
    head 47.19 M. A token meets 8 x 8 / 256 = 0.25 held experts. The
    latent layer's causal attention, 2 S x 32 x 320 / 2 a token; the
    recurrence, 6 x 128 x 128 a head and delta-rule layer."""
    dims = _real().dims
    d, H, D = 2304, 32, 128
    kda = 3 * d * H * D + 2 * (d * D + D * H * D) + d * H + H * D * d
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    expert = 3 * d * 1024
    assert flops_linear_moe.kda_params(dims) == kda == 39_460_864
    assert flops_linear_moe.mla_params(dims) == mla == 29_114_368
    assert flops_moe.expert_params(dims) == expert == 7_077_888
    assert flops_linear_moe.kinds(dims) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    outside = (4 * kda + mla + 3 * d * 9216 + 4 * (d * 256 + expert)
               + d * 20480)
    matmul = outside + 4 * 0.25 * expert
    assert matmul == 335_593_472
    S = 8192
    attention = 2 * S * 32 * (128 + 64 + 128) / 2
    recurrence = 4 * 6 * H * D * D
    assert flops_linear_moe.per_token(dims, S) == pytest.approx(
        3 * (2 * matmul + attention + recurrence), rel=1e-12)
    assert flops_linear_moe.per_token(dims, S) == pytest.approx(2.303e9,
                                                                rel=1e-3)
    # What XLA is held to: the grouped products at the dispatch
    # buffer's rows, eight a token, and with recomputation every
    # block's forward once more except the dense and shared `down`.
    buffered = 4 * 8 * expert
    plain = flops_linear_moe.matmul_params(dict(dims, remat=False))
    assert plain == outside + buffered
    again = (4 * kda + mla + 2 * d * 9216
             + 4 * (d * 256 + 2 * d * 1024 + 8 * expert))
    assert flops_linear_moe.matmul_params(dims) == pytest.approx(
        plain + again / 3, rel=1e-12)
    # The helpers the moe readers use read this cell's keyword names:
    # one dense block, four routed ones, 512 rows an expert a step.
    assert flops_moe.blocks(dims) == (1, 4, 0)
    tokens = 2 * 8192
    assert tokens * flops_moe.expected_expert_rows_per_token(dims) / 8 == 512


@pytest.mark.parametrize("backward,flops_want,bytes_want,ms", [
    # 8192 (batch row, head, chunk) programs of 8.905 MFLOP forward; the
    # tensors 0.942 GB: memory-bound at 1.150 ms.
    (False, 7.2946e10, 9.41621e8, 1.14972),
    (True, 1.45893e11, 1.749025e9, 2.13556),
])
def test_kda_cost_equals_the_hand_count(backward, flops_want, bytes_want,
                                        ms):
    """A chunk of 64 and a head of 128: the key scores 2016 pairs and the
    query scores 2080 at 256 FLOPs a pair, the solve 2016 pairs at 512,
    three state products of 2 x 64 x 128 x 128, P U~ 2080 pairs at 256.
    Bytes: q, k, v and the gate in bf16, g and beta in f32 in, o in
    bf16 out, every token and head; backward reads the inputs and dO and
    writes the inputs' gradients."""
    dims = _real().dims
    got_flops, got_bytes = flops_linear_moe.kda_cost(dims, 8192, 2,
                                                      backward)
    per_chunk = (2016 * 256 + 2080 * 256 + 2016 * 512
                 + 3 * 2 * 64 * 128 * 128 + 2080 * 256)
    passes = 2 if backward else 1
    assert got_flops == passes * per_chunk * 2 * 32 * 128
    assert got_flops == pytest.approx(flops_want, rel=1e-4)
    ins, outs = 4 * 128 * 2 + 128 * 4 + 4, 128 * 2
    per_token_head = passes * ins + outs
    assert got_bytes == 2 * 8192 * 32 * per_token_head
    assert got_bytes == pytest.approx(bytes_want, rel=1e-4)
    seconds, bound = flops.least_seconds(got_flops, got_bytes, V5E)
    assert bound == "memory" and seconds * 1e3 == pytest.approx(ms, rel=1e-4)


# ------------------------------------------------------------- the readers

def test_the_scope_name_is_the_programs():
    assert _kda.ATTN_KDA == tracing.SCOPE_ATTN_KDA


def test_the_cost_counts_the_programs_chunk():
    assert flops_linear_moe.CHUNK == kda.CHUNK


def _trace_of(step, steps=10) -> RegionTrace:
    ops, programs, t = [], [], 0.0
    for _ in range(steps):
        programs.append(Event("jit_train_step(1)", t, t + 0.05))
        for ms, stack in step:
            ops.append(Op("fusion", t, t + ms * 1e-3, stack, ""))
            t += ms * 1e-3
        t += 1e-3
    programs.append(Event("jit_train_step(1)", t, t + 0.05))
    return RegionTrace(ops=tuple(ops), programs=tuple(programs), spans=())


def test_the_delta_rule_readers_divide_least_time_by_traced_time(
        monkeypatch):
    """A step holds 10 ms of the delta rule's kernels forward, 10 more
    recomputed and 20 backward, 4 ms of its convolutions and gates, and
    6 ms of projections that are not the scope's: 44 ms under it; the
    least time of four layers' calls, 13.14 ms, is 29.9 % of it. A
    configuration without the delta rule reads nothing."""
    fwd = "jit(train_step)/jvp(M)/layer_1/attn/"
    bwd = "jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/layer_1/attn/"
    again = ("jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/"
             "rematted_computation/layer_1/attn/")
    step = [(10.0, fwd + "hvd.attn.kda/jit(_forward)/kda_fwd:"),
            (10.0, again + "hvd.attn.kda/jit(_forward)/kda_fwd:"),
            (20.0, bwd + "hvd.attn.kda/jit(_backward)/kda_bwd:"),
            (4.0, fwd + "hvd.attn.kda/q_conv/mul:"),
            (6.0, fwd + "hvd.attn.proj/q/dot_general:")]
    monkeypatch.setattr(_scopes, "_load", lambda path: _trace_of(step))
    ctx = types.SimpleNamespace(cell=_real(), peaks=V5E,
                                trace_file="made-up")
    assert kda_ms_per_step.compute(ctx) == pytest.approx(44.0)
    least = 4 * (1.14972 + 2.13556)
    assert kda_roofline.compute(ctx) == pytest.approx(100 * least / 44,
                                                      rel=1e-4)
    joyai = harness.load_cell(ROOT / "BENCHMARK.json", "joyai-s4096-b2-1c")
    other = types.SimpleNamespace(cell=joyai, peaks=V5E, trace_file="x")
    assert kda_roofline.compute(other) is None
    untraced = types.SimpleNamespace(cell=_real(), peaks=V5E,
                                     trace_file=None)
    assert kda_ms_per_step.compute(untraced) is None
    assert kda_roofline.compute(untraced) is None


# -------------------------------------------- the configuration and its model

def test_the_configuration_is_tied_to_the_model_it_builds():
    cell = _real()
    config, model = cell.config, harness.make_model(cell)
    cfg = model.cfg
    assert [(layer.mixer, layer.mlp) for layer in cfg.layers] == \
        flops_linear_moe.kinds(cell.dims)
    assert config["attention"] == {
        "heads": cfg.num_attention_heads,
        "qk_head_dim": cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "calls_per_step": sum(layer.mixer == "mla" for layer in cfg.layers)}
    assert config["kernels"][-2:] == ["kda_fwd", "kda_bwd"]
    assert (cfg.held, cfg.n_routed_experts, cfg.expert_share) == (8, 256, 0)
    assert cfg.attn_impl == "flash" and cfg.causal and cfg.remat
    assert cfg.q_lora_rank is None
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32)))["params"]
    leaves = {name: correct._leaf(shapes, path).value.shape
              for name, path in config["grad_leaves"].items()}
    assert leaves == {
        "embedding": (20480, 2304), "layer_1.f_b": (128, 4096),
        "layer_3.kv_b": (512, 32, 256),
        "layer_2.experts.gate_up": (8, 2304, 2, 1024),
        "final_norm.scale": (2304,)}
    count = sum(np.prod(leaf.value.shape) for leaf in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "value")))
    assert count == 602_433_408
    # Every key the file cut is stated beside its published value, and
    # the registry's defaults are the published ones.
    assert set(config["reduced"]) == set(config["published"]) <= set(
        config["changed"])
    published = LINEAR_MOE_CONFIGS["kimi-linear-48b-a3b"]
    for keyword, key in config["model_kwargs"].items():
        if key in config["reduced"] or keyword == "n_routed_experts":
            continue
        want = config[key]
        if keyword == "linear_attn_config":
            want = {k: tuple(v) if isinstance(v, list) else v
                    for k, v in want.items()}
            assert published.linear == want
            continue
        assert getattr(published, keyword) == want, keyword
    assert published.n_routed_experts == config["num_experts_published"] == \
        config["published"]["num_experts"]
    assert published.vocab_size == config["published"]["vocab_size"]
    assert published.num_hidden_layers == config["published"][
        "num_hidden_layers"]
    assert hasattr(gspmd, "lower") and cell.traffic["trainer"] == "gspmd"
    assert (cell.traffic["seq"], cell.traffic["batch_per_chip"]) == (8192, 2)

