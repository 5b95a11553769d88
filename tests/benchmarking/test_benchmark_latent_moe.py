"""The latent-attention mixture-of-experts configuration through the
benchmark, on the CPU at a tiny size: the cell files under
latent_cells/ (an index of their own; the benchmark's trainer,
reference, FLOP functions and per-layer readers found by name) run
through `run_cell` as `test_benchmark_harness.py` runs its toy; the same
model broken four ways has to fail the comparison; the FLOP functions
against a hand count; the real configuration's `attention`, `kernels`
and `grad_leaves` against the model it builds.
"""
import functools
import importlib
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, flops, flops_moe, harness, peaks
from benchmark.layer_metrics import _scopes, moe_experts_roofline
from benchmark.reference import latent_moe_ref
from benchmark.trace_regions import Op
from benchmark.trainers import gspmd_mtp, lm_objective
from horovod_tpu.common import tracing
from horovod_tpu.models import latent_moe
from horovod_tpu.parallel.train import lm_loss
from test_benchmark_harness import _check_contract, _run

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "latent_cells" / "cells.json"
CELL = "latent-tiny-1c"
REAL = "joyai-s4096-b2-1c"
SEQ = 128


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
        for name in ("latent_proj_ms_per_step", "moe_route_ms_per_step",
                     "moe_experts_ms_per_step", "moe_experts_roofline",
                     "mtp_ms_per_step"):
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
        "grad.layer_1.kv_b", "grad.layer_1.experts.gate_up", "grad.mtp.proj",
        "grad.final_norm.scale", "leaves_unmoved", "loss_after_20",
        "compiles_in_window", "losses_not_finite"}
    assert 0 < compared["choice_slack"]["value"] <= correct.CHOICE_TOL
    assert compared["leaves_unmoved"]["value"] == 0


# ------------------------------------------------------------ broken, four ways

def _pieces(**broken):
    cell = harness.load_cell(TINY, CELL)
    model = harness.make_model(cell)
    trainer = gspmd_mtp.build(model, cell.phases[0], jax.devices()[:1], 0)
    params = trainer.params(trainer.init())
    if broken:
        model = model.clone(cfg=model.cfg.__class__(
            **{**model.cfg.__dict__, **broken}))
    return cell, model, params


def _errors(cell, objective, params, reference=latent_moe_ref):
    return correct.measure_against_reference(
        objective, reference, params, cell.dims, SEQ, 1,
        cell.config["grad_leaves"])


def test_the_sound_model_passes_every_limit():
    cell, model, params = _pieces()
    errors = _errors(cell, gspmd_mtp.objective(model), params)
    assert correct.beyond_tolerance(errors) == {}, errors


def test_top_one_fewer_fails_on_the_choices():
    """One expert a token fewer than published is another model: the
    count is not k, the slack reads NaN."""
    cell, model, params = _pieces(num_experts_per_tok=1)
    errors = _errors(cell, gspmd_mtp.objective(model), params)
    assert np.isnan(errors["choice_slack"])
    assert "choice_slack" in correct.beyond_tolerance(errors)


def test_a_dropped_shared_expert_fails_on_the_logits():
    cell, model, params = _pieces(n_shared_experts=0)
    errors = _errors(cell, gspmd_mtp.objective(model), params)
    # (And on whatever follows: the next layer's router sees another
    # input than the reference's, so the slack fails too.)
    assert "logits" in correct.beyond_tolerance(errors)
    assert errors["logits"] > 2 * correct.LOGITS_TOL


def test_a_step_without_the_second_term_fails_on_the_gradients():
    """The step's objective left at the next-token loss, the reference
    at both terms: the logits agree, the gradients do not (the
    module's projection gets none at all)."""
    cell, model, params = _pieces()
    errors = _errors(cell, lm_objective(model, lm_loss), params)
    failed = correct.beyond_tolerance(errors)
    assert "logits" not in failed
    assert {"grad_norm", "grad.mtp.proj"} <= set(failed)
    assert errors["grad.mtp.proj"] == pytest.approx(1.0)


def test_values_read_at_the_query_key_width_fail(monkeypatch):
    """A head's keys and values come out of one projection as
    [k_nope | v]. Reading v where a head's query/key width ends (nope +
    rope) instead of where k_nope ends runs into the next head."""
    cell, model, params = _pieces()
    rot = model.cfg.qk_rope_head_dim
    dispatch = latent_moe._attention_dispatch

    def misread(cfg, q, k, v, mask):
        both = jnp.concatenate([k[..., :-rot], v], axis=-1)
        flat = jnp.roll(both.reshape(*both.shape[:2], -1), -rot, axis=-1)
        return dispatch(cfg, q, k,
                        flat.reshape(both.shape)[..., -v.shape[-1]:], mask)

    monkeypatch.setattr(latent_moe, "_attention_dispatch", misread)
    errors = _errors(cell, gspmd_mtp.objective(model), params)
    assert errors["logits"] > 5 * correct.LOGITS_TOL


def test_a_reference_given_another_share_fails():
    """The reference and the program are given the same share: share 0
    in the reference against share 1 in the program is another sum."""
    cell, model, params = _pieces()
    other = types.SimpleNamespace(
        forward=lambda p, ids, dims, choices: latent_moe_ref.forward(
            p, ids, dict(dims, expert_share=0), choices),
        loss=lambda p, ids, dims, choices: latent_moe_ref.loss(
            p, ids, dict(dims, expert_share=0), choices))
    errors = _errors(cell, gspmd_mtp.objective(model), params, other)
    assert "logits" in correct.beyond_tolerance(errors)


# ------------------------------------------------------------ the counting

def _real():
    return harness.load_cell(ROOT / "BENCHMARK.json", REAL)


def test_flops_of_the_share_equal_the_hand_count():
    """By hand from the published sizes (issue 30's reckoning): latent
    attention 26.35 M entries a layer, one expert 4.72 M, dense
    feed-forward 44.04 M, router 0.52 M, the module's projection 8.39 M,
    the head 33.10 M, twice; six blocks (one dense, four routed, the
    module's). A token meets 8 x 16 / 256 = 0.5 held experts."""
    dims = _real().dims
    d = 2048
    attention = (d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256
                 + 32 * 128 * d)
    expert = 3 * d * 768
    assert flops_moe.attention_params(dims) == attention == 26_345_472
    assert flops_moe.expert_params(dims) == expert == 4_718_592
    assert flops_moe.expected_expert_rows_per_token(dims) == 0.5
    outside = (6 * attention + 3 * d * 7168 + 5 * (d * 256 + expert)
               + 2 * d * d + 2 * d * 16160)
    matmul = outside + 5 * 0.5 * expert
    causal_attention = 4096 * 32 * (192 + 128) * 6
    assert flops_moe.routed(dims, 4096) == pytest.approx(
        3 * (2 * matmul + causal_attention), rel=1e-12)
    assert flops_moe.routed(dims, 4096) == pytest.approx(2.643e9, rel=1e-3)
    # What XLA is held to: the grouped products at the dispatch
    # buffer's rows, eight a token (a row for every pair), and with
    # recomputation every block's forward once more except the dense
    # and shared `down` and the module's projection.
    buffered = 5 * 8 * expert
    plain = flops_moe.matmul_params(dict(dims, remat=False))
    assert plain == pytest.approx(outside + buffered, rel=1e-12)
    again = (6 * attention + 2 * d * 7168
             + 5 * (d * 256 + 2 * d * 768 + 8 * expert))
    assert flops_moe.matmul_params(dims) == pytest.approx(
        plain + again / 3, rel=1e-12)


@pytest.mark.parametrize("backward", [False, True])
def test_grouped_product_cost_equals_the_hand_count(backward):
    """One routed layer at 4096 rows: 2 x 4096 x 4.72 M FLOPs forward
    (38.7 G), twice that backward; bytes: the 16 experts' bf16 weights
    (151 MB) and the rows in, between (gate, up, their product) and
    out."""
    dims = _real().dims
    got_flops, got_bytes = flops_moe.grouped_product_cost(4096, dims,
                                                          backward)
    passes = 2 if backward else 1
    assert got_flops == passes * 2 * 4096 * 4_718_592
    weights = 16 * 4_718_592 * 2
    rows = 4096 * (2 * 2048 + 3 * 768) * 2
    assert got_bytes == passes * (weights + rows)
    seconds, bound = flops.least_seconds(got_flops, got_bytes,
                                         peaks.PEAKS["TPU v5 lite"])
    assert bound == "memory" and seconds == pytest.approx(
        passes * 0.247e-3, rel=0.02)


# -------------------------------------------- the configuration and its model

def test_the_configuration_is_tied_to_the_model_it_builds():
    cell = _real()
    config, model = cell.config, harness.make_model(cell)
    cfg = model.cfg
    assert config["attention"] == {
        "heads": cfg.num_attention_heads, "qk_head_dim": cfg.qk_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "calls_per_step": (cfg.num_hidden_layers
                           + cfg.num_nextn_predict_layers)}
    assert (cfg.held, cfg.n_routed_experts, cfg.expert_share) == (16, 256, 0)
    assert cfg.attn_impl == "flash" and cfg.causal and cfg.remat
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(ids.shape, ids.dtype)))["params"]
    leaves = {name: correct._leaf(shapes, path).value.shape
              for name, path in config["grad_leaves"].items()}
    assert leaves == {
        "embedding": (16160, 2048), "layer_2.kv_b": (512, 32, 256),
        "layer_2.experts.gate_up": (16, 2048, 2, 768),
        "mtp.proj": (4096, 2048), "final_norm.scale": (2048,)}
    count = sum(np.prod(leaf.value.shape) for leaf in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "value")))
    assert count == pytest.approx(680e6, rel=0.005)
    # Every key the file cut is stated beside its published value.
    assert set(config["reduced"]) == set(config["published"]) == set(
        config["changed"]) - {"attn_impl", "remat"}
    trainer = importlib.import_module(
        f"benchmark.trainers.{cell.traffic['trainer']}")
    assert trainer.MTP_WEIGHT == config["mtp_loss_weight"] == \
        latent_moe_ref.MTP_WEIGHT
    assert hasattr(trainer, "lower")


def test_the_tiny_cell_lowers_with_its_kernels():
    """The v5e compile test holds the real cell to its `kernels` at the
    real size; here the tiny cell's lowered step (for the CPU: the
    kernels interpreted) names them all the same in its text."""
    cell = harness.load_cell(TINY, CELL)
    lowered = gspmd_mtp.lower(harness.make_model(cell), cell.phases[0],
                              jax.devices()[:1])
    text = lowered.as_text(debug_info=True)
    for name in cell.config["kernels"]:
        assert name in text, name


# ------------------------------------------------------------- the readers

def test_scope_readers_sum_the_ops_under_their_scope():
    """Two steps of 10 ms; a step holds 1 ms under the latent scope
    forward and 2 ms backward, 1 ms of routing, 2 ms of grouped products
    and 0.5 ms of the shared expert under the experts' scope, 1.5 ms of
    the module (of it 0.5 ms the module's own routing)."""
    assert (_scopes.ATTN_LATENT, _scopes.MOE_ROUTE, _scopes.MOE_EXPERTS,
            _scopes.MTP) == (tracing.SCOPE_ATTN_LATENT,
                             tracing.SCOPE_MOE_ROUTE,
                             tracing.SCOPE_MOE_EXPERTS, tracing.SCOPE_MTP)
    fwd, bwd = "jit(train_step)/jvp(M)/", "jit(train_step)/transpose(jvp(M))/"
    step = [
        (1.0, fwd + "layer_1/attn/hvd.attn.latent/q_a/dot_general:"),
        (2.0, bwd + "layer_1/attn/hvd.attn.latent/q_a/dot_general:"),
        (1.0, fwd + "layer_1/moe/hvd.moe.route/sort:"),
        (2.0, fwd + "layer_1/moe/while/body/hvd.moe.experts/gmm:"),
        (0.5, fwd + "layer_1/moe/hvd.moe.experts/shared/gate/dot_general:"),
        (1.0, fwd + "hvd.mtp/mtp/proj/dot_general:"),
        (0.5, bwd + "hvd.mtp/mtp/block/moe/hvd.moe.route/gather:"),
        (1.0, fwd + "layer_0/mlp/up/dot_general:"),
    ]
    ops, t = [], 0.0
    for _ in range(2):
        for ms, stack in step:
            ops.append(Op("fusion", t, t + ms * 1e-3, stack, ""))
            t += ms * 1e-3
        t += 1e-3
    window = (0.0, t)
    read = functools.partial(_scopes.seconds_per_step, ops, window, 2)
    assert read(_scopes.ATTN_LATENT) == pytest.approx(3e-3)
    assert read(_scopes.MOE_ROUTE) == pytest.approx(1.5e-3)
    assert read(_scopes.MOE_EXPERTS) == pytest.approx(2.5e-3)
    assert read(_scopes.MOE_EXPERTS, without=(
        moe_experts_roofline.SHARED_EXPERT,)) == pytest.approx(2e-3)
    assert read(_scopes.MTP) == pytest.approx(1.5e-3)
    assert read("hvd.nothing") is None
    # Half a window holds half the ops; an op across its edge is cut.
    assert _scopes.seconds_per_step(
        ops, (0.0, 0.0005), 1, _scopes.ATTN_LATENT) == pytest.approx(0.5e-3)


def test_the_roofline_reader_divides_least_time_by_traced_time(monkeypatch):
    """2 ms a step of grouped products traced, the real configuration at
    its traffic: five routed layers at 4096 rows, memory-bound at 0.247
    ms forward and twice that backward a layer: 3.71 ms least, over 2 ms
    ... reads above 100 only because the made-up time is too short; at
    10 ms it reads 37%."""
    cell = _real()
    ctx = types.SimpleNamespace(cell=cell, peaks=peaks.PEAKS["TPU v5 lite"],
                                trace_file="made-up")
    for traced_ms, want in ((10.0, 37.1), (None, None)):
        monkeypatch.setattr(
            moe_experts_roofline._scopes, "ms_per_step",
            lambda ctx, scope, without=(), ms=traced_ms: ms)
        got = moe_experts_roofline.compute(ctx)
        if want is None:
            assert got is None
        else:
            assert got == pytest.approx(want, rel=0.01)
