"""The grouped-query window / full mixture-of-experts configuration
through the benchmark, on the CPU at a tiny size: the cell files under
window_cells/ (an index of their own; the benchmark's trainer,
reference, FLOP functions and per-layer readers found by name) run
through `run_cell` as `test_benchmark_harness.py` runs its toy; the FLOP
and kernel-cost functions against a hand count; the two roofline
readers; the real configuration's `attention_calls`, `kernels` and
`grad_leaves` against the model it builds.
"""
import functools
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, flops, flops_moe, flops_window_moe, harness, peaks
from benchmark.layer_metrics import (
    _attention_calls, _scopes, full_attention_roofline,
    window_attention_roofline)
from benchmark.trace_regions import Op
from benchmark.trainers import gspmd
from horovod_tpu.common import tracing
from horovod_tpu.models.window_moe import FULL, SLIDING, WINDOW_MOE_CONFIGS
from test_benchmark_harness import _check_contract, _run

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "window_cells" / "cells.json"
CELL = "window-tiny-1c"
REAL = "laguna-s8192-b2-1c"
NEW_READERS = ("attn_proj_ms_per_step", "window_attn_ms_per_step",
               "full_attn_ms_per_step", "window_attention_roofline",
               "full_attention_roofline")
V5E = peaks.PEAKS["TPU v5 lite"]


def _real():
    return harness.load_cell(ROOT / "BENCHMARK.json", REAL)


# ------------------------------------------------------- through run_cell

@pytest.mark.parametrize("trace", [False, True])
def test_the_tiny_cell_runs_to_the_contract(trace, monkeypatch, capsys):
    cell = harness.load_cell(TINY, CELL)
    result = _run(TINY, CELL, trace, monkeypatch)
    declared = dict(cell.per_layer if trace else cell.end_to_end)
    if trace:
        # The made-up chip trace holds none of the model's own scopes:
        # their readers find nothing and their metrics are left out, as
        # on a parent of the scopes.
        for name in NEW_READERS + ("moe_route_ms_per_step",
                                   "moe_experts_ms_per_step",
                                   "moe_experts_roofline"):
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
        "grad.layer_1.k", "grad.layer_1.gate", "grad.layer_2.q",
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
            tracing.SCOPE_ATTN_PROJ, tracing.SCOPE_ATTN_WINDOW,
            tracing.SCOPE_ATTN_FULL, tracing.SCOPE_MOE_ROUTE,
            tracing.SCOPE_MOE_EXPERTS]:
        assert name in text, name


# ------------------------------------------------------------ the counting

def test_flops_of_the_share_equal_the_hand_count():
    """By hand from the published sizes (issue 35's reckoning): a full
    layer's attention 29.46 M entries (q and o 12.58 each, k and v 4.19
    together, the gate 0.10), a sliding layer's 37.88 M, the dense
    feed-forward 50.33 M, a router 0.52 M, the shared expert and one
    expert 3.15 M each, the head 25.69 M. A token meets 8 x 16 / 256 =
    0.5 held experts. Attention by visible pairs: the causal triangle on
    the two full layers, the band of 512 on the three sliding ones."""
    dims = _real().dims
    d, dh = 2048, 128
    full = 2 * d * 48 * dh + 2 * d * 8 * dh + d * 48
    sliding = 2 * d * 64 * dh + 2 * d * 8 * dh + d * 64
    expert = 3 * d * 512
    assert flops_window_moe.attention_params(dims, 48) == full == 29_458_432
    assert flops_window_moe.attention_params(dims, 64) == sliding == 37_879_808
    assert flops_moe.expert_params(dims) == expert == 3_145_728
    assert flops_moe.expected_expert_rows_per_token(dims) == 0.5
    assert flops_window_moe.layers(dims) == [
        (FULL, 48, "dense"), (SLIDING, 64, "sparse"),
        (SLIDING, 64, "sparse"), (SLIDING, 64, "sparse"),
        (FULL, 48, "sparse")]
    outside = (2 * full + 3 * sliding + 3 * d * 8192
               + 4 * (d * 256 + expert) + d * 12544)
    matmul = outside + 4 * 0.5 * expert
    assert matmul == pytest.approx(269.6e6, rel=1e-3)
    S, W = 8192, 512
    pairs_full, pairs_window = S * (S + 1) / 2, W * S - W * (W - 1) / 2
    assert flops_window_moe.visible_pairs(S, None) == pairs_full
    assert flops_window_moe.visible_pairs(S, W) == pairs_window
    assert flops_window_moe.visible_pairs(300, W) == 300 * 301 / 2
    attention = (2 * 4 * dh * 48 * pairs_full
                 + 3 * 4 * dh * 64 * pairs_window) / S
    assert attention == pytest.approx(201e6 + 49e6, rel=5e-3)
    assert flops_window_moe.per_token(dims, S) == pytest.approx(
        3 * (2 * matmul + attention), rel=1e-12)
    assert flops_window_moe.per_token(dims, S) == pytest.approx(2.37e9,
                                                                rel=2e-3)
    # What XLA is held to: the grouped products at the dispatch
    # buffer's rows, eight a token, and with recomputation every
    # block's forward once more except the dense and shared `down`.
    buffered = 4 * 8 * expert
    plain = flops_window_moe.matmul_params(dict(dims, remat=False))
    assert plain == pytest.approx(outside + buffered, rel=1e-12)
    again = (2 * full + 3 * sliding + 2 * d * 8192
             + 4 * (d * 256 + 2 * d * 512 + 8 * expert))
    assert flops_window_moe.matmul_params(dims) == pytest.approx(
        plain + again / 3, rel=1e-12)
    # The helpers the moe readers use read this cell's keyword names.
    assert flops_moe.blocks(dims) == (1, 4, 0)


@pytest.mark.parametrize("call,backward,flops_want,bytes_want,ms,bound", [
    # 2 x 48 heads x 33.56 M pairs x 512 FLOPs a pair = 1.649 T forward.
    ("full", False, 1.6495e12, 4.729e8, 8.373, "compute"),
    ("full", True, 3.2989e12, 9.427e8, 16.746, "compute"),
    # 2 x 64 heads x 4.06 M pairs x 512 = 0.266 T: 1.35 ms of the MXU,
    # over the 0.74 ms its 0.61 GB take at the HBM peak.
    ("window", False, 2.6630e11, 6.082e8, 1.352, "compute"),
    ("window", True, 5.3261e11, 1.2122e9, 2.704, "compute"),
])
def test_attention_call_cost_equals_the_hand_count(call, backward, flops_want,
                                                   bytes_want, ms, bound):
    cell = _real()
    shape = next(c for c in cell.config["attention_calls"]
                 if (c["window"] is not None) == (call == "window"))
    got_flops, got_bytes = flops_window_moe.attention_call_cost(
        2, 8192, shape["heads"], shape["kv_heads"], shape["head_dim"],
        shape["window"], backward)
    pairs = flops_window_moe.visible_pairs(8192, shape["window"])
    passes = 2 if backward else 1
    assert got_flops == passes * 2 * 256 * 2 * shape["heads"] * pairs
    assert got_flops == pytest.approx(flops_want, rel=1e-3)
    tensor = 2 * 8192 * 128 * 2
    rows = 2 * shape["heads"] * 8192 * 4
    at_heads, at_kv = (4, 4) if backward else (2, 2)
    assert got_bytes == (at_heads * shape["heads"] + at_kv * 8) * tensor + rows
    assert got_bytes == pytest.approx(bytes_want, rel=1e-3)
    seconds, bound_got = flops.least_seconds(got_flops, got_bytes, V5E)
    assert bound_got == bound and seconds * 1e3 == pytest.approx(ms, rel=2e-3)


# ------------------------------------------------------------- the readers

def test_scope_names_are_the_programs():
    assert (_attention_calls.ATTN_PROJ, _attention_calls.ATTN_WINDOW,
            _attention_calls.ATTN_FULL) == (
        tracing.SCOPE_ATTN_PROJ, tracing.SCOPE_ATTN_WINDOW,
        tracing.SCOPE_ATTN_FULL)
    assert (_scopes.MOE_ROUTE, _scopes.MOE_EXPERTS) == (
        tracing.SCOPE_MOE_ROUTE, tracing.SCOPE_MOE_EXPERTS)


def test_scope_readers_sum_the_ops_under_their_scope():
    """Two steps; a step holds 1 ms of projections forward and 2
    backward, a windowed kernel call of 0.5 ms forward, once more
    recomputed and 1 ms backward, a full one of 1.5 / 1.5 / 3 ms."""
    fwd, bwd = "jit(train_step)/jvp(M)/", "jit(train_step)/transpose(jvp(M))/"
    again = bwd + "checkpoint/rematted_computation/"
    step = [
        (1.0, fwd + "layer_1/attn/hvd.attn.proj/q/dot_general:"),
        (2.0, bwd + "layer_1/attn/hvd.attn.proj/q/dot_general:"),
        (0.5, fwd + "layer_1/attn/hvd.attn.window/jit(_fwd_call)/fwd:"),
        (0.5, again + "layer_1/attn/hvd.attn.window/jit(_fwd_call)/fwd:"),
        (1.0, bwd + "layer_1/attn/hvd.attn.window/jit(_bwd_call)/bwd:"),
        (1.5, fwd + "layer_0/attn/hvd.attn.full/jit(_fwd_call)/fwd:"),
        (1.5, again + "layer_0/attn/hvd.attn.full/jit(_fwd_call)/fwd:"),
        (3.0, bwd + "layer_0/attn/hvd.attn.full/jit(_bwd_call)/bwd:"),
        (1.0, fwd + "layer_0/mlp/up/dot_general:"),
    ]
    ops, t = [], 0.0
    for _ in range(2):
        for ms, stack in step:
            ops.append(Op("fusion", t, t + ms * 1e-3, stack, ""))
            t += ms * 1e-3
        t += 1e-3
    read = functools.partial(_scopes.seconds_per_step, ops, (0.0, t), 2)
    assert read(_attention_calls.ATTN_PROJ) == pytest.approx(3e-3)
    assert read(_attention_calls.ATTN_WINDOW) == pytest.approx(2e-3)
    assert read(_attention_calls.ATTN_FULL) == pytest.approx(6e-3)


@pytest.mark.parametrize("reader,least_ms", [
    # three windowed calls of 1.352 + 2.703 ms, two full ones of
    # 8.373 + 16.745 ms (the hand counts above).
    (window_attention_roofline, 3 * 4.056),
    (full_attention_roofline, 2 * 25.119),
])
def test_the_roofline_readers_divide_least_time_by_traced_time(
        reader, least_ms, monkeypatch):
    cell = _real()
    ctx = types.SimpleNamespace(cell=cell, peaks=V5E, trace_file="made-up")
    for traced_ms in (100.0, None):
        monkeypatch.setattr(_attention_calls._scopes, "ms_per_step",
                            lambda ctx, scope, without=(), ms=traced_ms: ms)
        got = reader.compute(ctx)
        if traced_ms is None:
            assert got is None
        else:
            assert got == pytest.approx(least_ms, rel=2e-3)
    # A configuration without the key (every other cell's) reads nothing.
    bare = types.SimpleNamespace(
        cell=types.SimpleNamespace(config={}, traffic=cell.traffic),
        peaks=V5E, trace_file="made-up")
    monkeypatch.setattr(_attention_calls._scopes, "ms_per_step",
                        lambda ctx, scope, without=(): 5.0)
    assert reader.compute(bare) is None


# -------------------------------------------- the configuration and its model

def test_the_configuration_is_tied_to_the_model_it_builds():
    cell = _real()
    config, model = cell.config, harness.make_model(cell)
    cfg = model.cfg
    assert len(cfg.layers) == cfg.num_hidden_layers == 5
    kinds = {kind: [layer for layer in cfg.layers if layer.attention == kind]
             for kind in (FULL, SLIDING)}
    assert config["attention_calls"] == [
        {"heads": kinds[FULL][0].heads, "kv_heads": cfg.num_key_value_heads,
         "head_dim": cfg.head_dim, "window": None,
         "calls_per_step": len(kinds[FULL])},
        {"heads": kinds[SLIDING][0].heads,
         "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
         "window": cfg.sliding_window, "calls_per_step": len(kinds[SLIDING])}]
    assert "attention" not in config
    assert (cfg.held, cfg.n_routed_experts, cfg.expert_share) == (16, 256, 0)
    assert cfg.attn_impl == "flash" and cfg.causal and cfg.remat
    assert cfg.rotary(FULL).rope_type == "yarn"
    assert cfg.rotary(FULL).partial_rotary_factor == 0.5
    assert cfg.rotary(SLIDING).rope_theta == 10000
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32)))["params"]
    leaves = {name: correct._leaf(shapes, path).value.shape
              for name, path in config["grad_leaves"].items()}
    assert leaves == {
        "embedding": (12544, 2048), "layer_2.k": (2048, 8, 128),
        "layer_2.gate": (2048, 64), "layer_4.q": (2048, 48, 128),
        "layer_3.experts.gate_up": (16, 2048, 2, 512),
        "final_norm.scale": (2048,)}
    count = sum(np.prod(leaf.value.shape) for leaf in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "value")))
    assert count == pytest.approx(490.3e6, rel=2e-4)
    # Every key the file cut is stated beside its published value, and
    # the registry's defaults are the published ones.
    assert set(config["reduced"]) == set(config["published"]) <= set(
        config["changed"])
    published = WINDOW_MOE_CONFIGS["laguna-xs2"]
    for keyword, key in config["model_kwargs"].items():
        if key in config["reduced"] or keyword in (
                "n_routed_experts", "first_k_dense_replace", "rope_parameters"):
            continue
        want = config[key]
        assert getattr(published, keyword) == (
            tuple(want) if isinstance(want, list) else want), keyword
    assert published.n_routed_experts == config["num_experts_published"] == \
        config["published"]["num_experts"]
    assert published.vocab_size == config["published"]["vocab_size"]
    assert published.num_hidden_layers == config["published"][
        "num_hidden_layers"] == len(config["layer_types"])
    assert dict(published.rope_parameters) == dict(cfg.rope_parameters)
    assert [a[0] for a in config["assumed"][:5]] == ["("] * 5
    assert hasattr(gspmd, "lower") and cell.traffic["trainer"] == "gspmd"
    assert (cell.traffic["seq"], cell.traffic["batch_per_chip"]) == (8192, 2)
