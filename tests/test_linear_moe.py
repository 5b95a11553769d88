"""The hybrid delta-rule / latent-attention decoder
(`horovod_tpu/models/linear_moe.py`; the registry's `kimi-linear-48b-a3b`
at a size the CPU runs) against its plain reference
(`benchmark/reference/linear_moe_ref.py`, whose delta rule runs one
position at a time) on seeded weights: within the benchmark's limits
(`benchmark/correct.py`) with bfloat16 activations on the dense and the
flash path, every gradient within 1e-4 with float32 activations, not
within them with a part of the delta rule broken; the guide's share test
of the routed layer; the published defaults and the cut; the scope and
the gauges of the new block."""
import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct
from benchmark.reference import linear_moe_ref
from benchmark.trainers import lm_objective
from horovod_tpu.common import telemetry, tracing
from horovod_tpu.models import get_model, linear_moe
from horovod_tpu.models.linear_moe import KDA, LINEAR_MOE_CONFIGS, MLA
from horovod_tpu.ops import kda
from horovod_tpu.parallel.train import lm_loss

TINY = LINEAR_MOE_CONFIGS["linear-moe-tiny"]
SEQ = 96          # a chunk and a half of 64
# What the cell's file compares leaf by leaf, in the tiny model, but
# `A_log`, whose bf16 gradient the global limit does not hold (PERF.md,
# section 7); `test_every_gradient_agrees_in_float32` holds it.
LEAVES = {"embedding": ["embed", "embedding"],
          "layer_1.f_b": ["layer_1", "attn", "f_b", "kernel"],
          "layer_2.kv_b": ["layer_2", "attn", "kv_b", "kernel"],
          "layer_1.experts.gate_up": ["layer_1", "moe", "gate_up"],
          "final_norm.scale": ["final_norm", "scale"]}


def _dims(cfg=TINY, **over):
    """The model's keyword arguments as a configuration file gives them
    (`benchmark.harness.Cell.dims`): what the reference reads."""
    dims = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name not in ("dtype", "param_dtype", "logits_dtype")}
    dims["linear_attn_config"] = cfg.linear
    return {**dims, "experts_held": 4, "expert_share": 1, **over}


def _model(**over):
    return get_model("linear-moe-tiny").make_model(**_dims(**over))


def _params(seed=0):
    ids = jnp.zeros((1, SEQ), jnp.int32)
    return flax.core.meta.unbox(
        _model().init(jax.random.PRNGKey(seed), ids)["params"])


def _every_leaf(params):
    return {"/".join(str(k.key) for k in path): [k.key for k in path]
            for path, _ in jax.tree_util.tree_leaves_with_path(params)}


def _errors(model, params, leaves, **dims):
    return correct.measure_against_reference(
        lm_objective(model, lm_loss), linear_moe_ref, params,
        _dims(**dims), SEQ, 1, leaves)


@pytest.fixture(scope="module")
def params():
    return _params()


# ------------------------------------------------ the model and its reference

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_program_agrees_with_the_plain_reference(params, impl):
    """Logits at every position, the gradient's global norm, the leaves
    the cell's file names and the slack of the choices, bf16 activations
    against float32, under the benchmark's limits; the flash path
    interpreted, each block recomputed, as the cell runs it."""
    model = _model(attn_impl=impl, remat=impl == "flash")
    errors = _errors(model, params, LEAVES)
    assert correct.beyond_tolerance(errors) == {}, errors
    assert set(errors) == {"logits", "choice_slack", "grad_norm",
                           *(f"grad.{name}" for name in LEAVES)}
    assert errors["choice_slack"] <= correct.CHOICE_TOL / 2


def test_every_gradient_agrees_in_float32(params):
    """With float32 activations the chunked kernels, the block and the
    one-position-at-a-time reference agree to 1e-4 on the logits and on
    EVERY leaf's gradient. (In bfloat16 `A_log`'s gradient, a sum over
    every position and channel of a head that mostly cancels, reads
    0.10-0.11 of its largest entry at 128 positions, on both paths; no
    other leaf comes near the limit.)"""
    model = _model(attn_impl="flash", remat=True, dtype=jnp.float32,
                   logits_dtype=jnp.float32)
    errors = _errors(model, params, _every_leaf(params), attn_impl="flash")
    assert {k: v for k, v in errors.items()
            if not v <= 1e-4 and k != "choice_slack"} == {}
    assert errors["choice_slack"] == 0.0


def _broken_rule(change):
    rule = kda.kda
    return lambda q, k, v, g, beta, **kw: rule(*change(q, k, v, g, beta), **kw)


@pytest.mark.parametrize("fault,change", [
    ("decay-left-out", lambda q, k, v, g, b: (q, k, v, 0 * g, b)),
    ("write-strength-one", lambda q, k, v, g, b: (q, k, v, g, 1 + 0 * b)),
    ("keys-for-queries", lambda q, k, v, g, b: (k, k, v, g, b)),
])
def test_a_broken_delta_rule_fails_on_the_logits(params, fault, change,
                                                 monkeypatch):
    """Each fault leaves the shapes as they are and moves the logits
    beyond LOGITS_TOL."""
    monkeypatch.setattr(kda, "kda", _broken_rule(change))
    errors = _errors(_model(), params, {})
    assert errors["logits"] > correct.LOGITS_TOL, (fault, errors)


# -------------------------------------------------------------- the shares

def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: four shares of two experts each, the
    shared expert counted once: the routed parts of all shares plus the
    shared expert equal what the uncut reference gives for the whole
    layer; and one share alone is the reference's for that share."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    layer = lambda c: linear_moe.RoutedExperts(c)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    params = flax.core.meta.unbox(
        layer(cfg).init(jax.random.PRNGKey(1), x))["params"]
    params = dict(params, router=params["router"] * 5)
    uncut = _dims(cfg, experts_held=None, expert_share=0)
    whole, _ = linear_moe_ref._routed(x, params, uncut, None)
    parts = jnp.zeros_like(x)
    for share in range(4):
        part = dataclasses.replace(cfg, experts_held=2, expert_share=share,
                                   n_shared_experts=0)
        held = {"router": params["router"],
                "gate_up": params["gate_up"][2 * share:2 * share + 2],
                "down": params["down"][2 * share:2 * share + 2]}
        parts = parts + layer(part).apply({"params": held}, x)
    shared = params["shared"]
    parts = parts + linear_moe_ref._mlp(
        x, shared["gate"]["kernel"], shared["up"]["kernel"],
        shared["down"]["kernel"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    held = dict(params, gate_up=params["gate_up"][6:], down=params["down"][6:])
    one = layer(dataclasses.replace(cfg, experts_held=2, expert_share=3)
                ).apply({"params": held}, x)
    want, _ = linear_moe_ref._routed(
        x, held, dict(uncut, experts_held=2, expert_share=3), None)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the registry

def _parameters(**over) -> int:
    model = get_model("kimi-linear-48b-a3b").make_model(**over)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_the_registry_entry_is_the_published_model():
    """Kimi-Linear-48B-A3B's published defaults build 49.12 B parameters
    from shapes alone, 48.37 B without the embedding and the head (the
    published "48B"); layers 4, 8, ..., 24 and 27 are latent attention,
    the first layer's feed-forward alone is dense. The cell's cut, five
    layers with 8 of 256 experts and an eighth of the vocabulary, holds
    602.4 M."""
    layers = LINEAR_MOE_CONFIGS["kimi-linear-48b-a3b"].layers
    assert len(layers) == 27
    assert [n + 1 for n, layer in enumerate(layers) if layer.mixer == MLA] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert layers[:5] == (linear_moe.Layer(KDA, "dense"),) + tuple(
        linear_moe.Layer(m, "sparse") for m in (KDA, KDA, MLA, KDA))
    count = _parameters()
    assert count == pytest.approx(49.12e9, rel=5e-4)
    assert count - 2 * 163840 * 2304 == pytest.approx(48.37e9, rel=5e-4)
    assert _parameters(num_hidden_layers=5, experts_held=8,
                       vocab_size=20480) == 602_433_408


@pytest.mark.parametrize("broken", [
    {"num_hidden_layers": 4},                    # layer 4 is in no list
    {"linear_attn_config": {**TINY.linear, "kda_layers": (1, 2, 3)}},
    {"experts_held": 3},
    {"q_lora_rank": 16},
    {"mla_use_nope": False},
])
def test_a_configuration_that_contradicts_itself_is_refused(broken):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **broken)


# ---------------------------------------------------- scopes and gauges

def test_the_kda_scope_is_a_name_of_its_own():
    scopes = [getattr(tracing, name) for name in dir(tracing)
              if name.startswith("SCOPE_")]
    assert tracing.SCOPE_ATTN_KDA == "hvd.attn.kda"
    assert not [(a, b) for a in scopes for b in scopes
                if a != b and a in b]


def test_the_lowered_step_names_the_delta_rule_forward_and_backward(params):
    """The block's own work is under `hvd.attn.kda` in the forward and in
    the backward pass, its kernels behind their jits; the projections
    under `hvd.attn.proj`, the latent layer's under `hvd.attn.latent`."""
    model = _model(remat=True)
    ids = jnp.zeros((1, SEQ), jnp.int32)
    text = jax.jit(jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, ids), ids))).lower(params).as_text(
            debug_info=True)
    kda_scope = tracing.SCOPE_ATTN_KDA
    backward = "transpose(jvp(LinearMoELM))/jvp(LinearMoELM)/checkpoint/"
    assert f"jvp(LinearMoELM)/layer_0/attn/{kda_scope}/jit(_forward)" in text
    assert f"{backward}layer_1/attn/{kda_scope}/jit(_backward)" in text
    assert (f"{backward}rematted_computation/layer_1/attn/{kda_scope}/"
            "jit(_forward)") in text
    assert f"layer_0/attn/{tracing.SCOPE_ATTN_PROJ}/q/" in text
    assert f"layer_2/attn/{tracing.SCOPE_ATTN_LATENT}/" in text
    assert f"layer_2/attn/{kda_scope}" not in text


def test_the_gauges_count_chunks_and_states_where_the_block_is_traced():
    """1000 positions in chunks of 64 (16, the last one padded), two
    batch rows of 4 heads of 16 x 16 float32 states, all 4 heads in one
    program."""
    model = _model()
    ids = jnp.zeros((2, 1000), jnp.int32)
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    assert telemetry.gauge("horovod_kda_chunks").value == 16
    assert telemetry.gauge("horovod_kda_heads_per_program").value == 4
    carried = 2 * 4 * 16 * 16 * 4
    assert {what: telemetry.gauge("horovod_kda_state_bytes",
                                  labels={"what": what}).value
            for what in ("carried", "stored")} == {
        "carried": carried, "stored": 16 * carried}
