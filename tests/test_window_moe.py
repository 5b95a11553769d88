"""The grouped-query decoder with sliding-window and full layers
(`horovod_tpu/models/window_moe.py`; the registry's `laguna-xs2` at a
size the CPU runs) against its plain reference
(`benchmark/reference/window_moe_ref.py`) on seeded weights, through the
comparison the benchmark makes (`benchmark/correct.py`, its limits as
they stand): sound on the dense and the flash (interpreted) path, and
not sound, by a named limit, with each part of the attention broken;
the guide's share test of the routed layer; the published defaults.
"""
import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct
from benchmark.reference import window_moe_ref
from benchmark.trainers import lm_objective
from horovod_tpu.models import get_model, window_moe
from horovod_tpu.models.window_moe import FULL, SLIDING, WINDOW_MOE_CONFIGS
from horovod_tpu.parallel.train import lm_loss

TINY = WINDOW_MOE_CONFIGS["window-moe-tiny"]
SEQ = 64          # four times the tiny configuration's original context


def _dims(cfg=TINY, **over):
    """The model's keyword arguments as a configuration file gives them
    (`benchmark.harness.Cell.dims`): what the reference reads."""
    dims = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name not in ("dtype", "param_dtype", "logits_dtype")}
    dims["rope_parameters"] = {kind: dataclasses.asdict(rope)
                               for kind, rope in cfg.rope_parameters}
    return {**dims, "experts_held": 4, "expert_share": 1, **over}


def _model(**over):
    return get_model("window-moe-tiny").make_model(**_dims(**over))


def _params(seed=0, scale=4.0):
    """Seeded weights, the matrices `scale` times the initializer's 0.02
    so that attention is far from uniform (a window one key wider then
    moves the logits by more than rounding does)."""
    model = _model()
    ids = jnp.zeros((1, SEQ), jnp.int32)
    params = flax.core.meta.unbox(
        model.init(jax.random.PRNGKey(seed), ids)["params"])
    return jax.tree.map(lambda a: a * scale if a.ndim > 1 else a, params)


def _every_leaf(params):
    return {"/".join(str(k.key) for k in path): [k.key for k in path]
            for path, _ in jax.tree_util.tree_leaves_with_path(params)}


def _errors(model, params, dims=None, fault=None, reference=window_moe_ref):
    objective = lm_objective(model, lm_loss)
    if fault is not None:
        sound = objective
        objective = lambda p, ids, n: sound(fault(p), ids, n)
    return correct.measure_against_reference(
        objective, reference, params, dims or _dims(), SEQ, 1,
        _every_leaf(params))


@pytest.fixture(scope="module")
def params():
    return _params()


# ------------------------------------------------ the model and its reference

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_program_agrees_with_the_plain_reference(params, impl):
    """Logits at every position, the loss gradient of EVERY leaf and the
    slack of the choices, bf16 activations against float32, under the
    benchmark's limits; the flash path interpreted, each block
    recomputed, as the cell runs it."""
    model = _model(attn_impl=impl, remat=impl == "flash")
    errors = _errors(model, params)
    assert correct.beyond_tolerance(errors) == {}, errors
    assert {"logits", "choice_slack", "grad_norm",
            "grad.layer_1/attn/gate/kernel", "grad.layer_1/moe/gate_up",
            "grad.layer_2/attn/k/kernel"} <= set(errors)
    assert errors["choice_slack"] <= correct.CHOICE_TOL / 2


def _with_rope(kind, **changed):
    rope = {k: dataclasses.asdict(v) for k, v in TINY.rope_parameters}
    rope[kind] = {**rope[kind], **changed}
    return rope


def _through_the_door(monkeypatch, broken):
    """`broken(attention, q, k, v, **kw)` in place of the door the model
    calls."""
    door = window_moe.attention
    monkeypatch.setattr(
        window_moe, "attention",
        lambda q, k, v, **kw: broken(door, q, k, v, **kw))


def _full_heads_on_a_sliding_layer(door, q, k, v, **kw):
    """A sliding layer computed with the full layers' head count: its
    last query heads never attend."""
    out = door(q, k, v, **kw)
    if kw["window"] is None:
        return out
    full = TINY.num_attention_heads_per_layer[0]
    return out.at[:, :, full:].set(0)


def _heads_mapped_modulo(door, q, k, v, **kw):
    """Query head h attends key/value head h % H_kv instead of
    h // (H / H_kv)."""
    group = q.shape[2] // k.shape[2]
    return door(q, jnp.tile(k, (1, 1, group, 1)),
                jnp.tile(v, (1, 1, group, 1)), **kw)


_rotary_tables = window_moe.rotary_tables


def _plain_rotary_past_the_original_context(positions, head_dim, rope):
    """YaRN's frequencies up to `original_max_position_embeddings`,
    plain rotary's behind it."""
    cos, sin = _rotary_tables(positions, head_dim, rope)
    if rope.rope_type != "yarn":
        return cos, sin
    plain = dataclasses.replace(rope, rope_type="default")
    cos_p, sin_p = _rotary_tables(positions, head_dim, plain)
    far = (positions >= rope.original_max_position_embeddings)[:, None]
    return jnp.where(far, cos_p, cos), jnp.where(far, sin_p, sin)


def _without_the_gate(params):
    """sigmoid(0) = 1/2 on every head and the output projection doubled:
    the attention of a program that has no gate."""
    def fix(path, leaf):
        keys = [k.key for k in path]
        if keys[-2:] == ["gate", "kernel"] and "attn" in keys:
            return jnp.zeros_like(leaf)
        if keys[-2:] == ["o", "kernel"]:
            return 2 * leaf
        return leaf
    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.mark.parametrize("fault", [
    "window-one-key-too-wide", "window-one-key-too-narrow",
    "full-head-count-on-a-sliding-layer", "kv-heads-mapped-modulo",
    "rotary-on-all-dims-of-a-full-layer", "attention-factor-left-out",
    "gate-left-out", "plain-rotary-past-the-original-context"])
def test_a_broken_attention_fails_on_the_logits(params, fault, monkeypatch):
    """Each fault is a reading of the published keys that a program
    could plausibly make; each moves the logits beyond LOGITS_TOL (and
    the gradients with them)."""
    over, objective_fault = {}, None
    if fault == "window-one-key-too-wide":
        over = {"sliding_window": TINY.sliding_window + 1}
    elif fault == "window-one-key-too-narrow":
        over = {"sliding_window": TINY.sliding_window - 1}
    elif fault == "full-head-count-on-a-sliding-layer":
        _through_the_door(monkeypatch, _full_heads_on_a_sliding_layer)
    elif fault == "kv-heads-mapped-modulo":
        _through_the_door(monkeypatch, _heads_mapped_modulo)
    elif fault == "rotary-on-all-dims-of-a-full-layer":
        over = {"rope_parameters": _with_rope(FULL, partial_rotary_factor=1)}
    elif fault == "attention-factor-left-out":
        over = {"rope_parameters": _with_rope(FULL, attention_factor=1.0)}
    elif fault == "gate-left-out":
        objective_fault = _without_the_gate
    else:
        monkeypatch.setattr(window_moe, "rotary_tables",
                            _plain_rotary_past_the_original_context)
    errors = _errors(_model(**over), params, fault=objective_fault)
    failed = correct.beyond_tolerance(errors)
    assert "logits" in failed, errors
    assert errors["logits"] > 2 * correct.LOGITS_TOL
    assert any(name.startswith("grad") for name in failed)


def test_yarn_frequencies_follow_the_five_keys():
    """Laguna-XS.2's full layers: 32 pairs of a 64-wide slice at theta
    500000. A pair that makes more than beta_fast = 64 turns in 4096
    positions keeps its frequency (pairs 0-5: the ramp starts at
    floor(32 ln(4096 / (2 pi 64)) / ln 500000) = 5), one that makes
    fewer than beta_slow = 1 gets it over factor = 64 (from pair
    ceil(32 ln(4096 / (2 pi)) / ln 500000) = 16), and between them the
    blend is linear in the pair index. The reference computes the same
    from the same keys, written on its own."""
    rope = WINDOW_MOE_CONFIGS["laguna-xs2"].rotary(FULL)
    got = window_moe.yarn_inv_freq(64, rope)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-12)
    ramp = (10 - 5) / (16 - 5)
    np.testing.assert_allclose(
        got[10], plain[10] * (1 - ramp) + plain[10] / 64 * ramp, rtol=1e-12)
    np.testing.assert_allclose(
        window_moe_ref._yarn_frequencies(64, dataclasses.asdict(rope)), got,
        rtol=1e-12)
    assert rope.attention_factor == pytest.approx(0.1 * np.log(64) + 1)


# -------------------------------------------------------------- the shares

def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: four shares of two experts each, the
    shared expert counted once: the routed parts of all shares plus the
    shared expert equal what the uncut reference gives for the whole
    layer; and one share alone is the reference's for that share."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    layer = lambda c: window_moe.RoutedExperts(c)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    params = flax.core.meta.unbox(
        layer(cfg).init(jax.random.PRNGKey(1), x))["params"]
    params = dict(params, router=params["router"] * 5)
    uncut = _dims(cfg, experts_held=None, expert_share=0)
    whole, _ = window_moe_ref._routed(x, params, uncut, None)
    parts = jnp.zeros_like(x)
    for share in range(4):
        part = dataclasses.replace(cfg, experts_held=2, expert_share=share,
                                   shared_expert_intermediate_size=0)
        held = {"router": params["router"],
                "gate_up": params["gate_up"][2 * share:2 * share + 2],
                "down": params["down"][2 * share:2 * share + 2]}
        parts = parts + layer(part).apply({"params": held}, x)
    shared = params["shared"]
    parts = parts + window_moe_ref._mlp(
        x, shared["gate"]["kernel"], shared["up"]["kernel"],
        shared["down"]["kernel"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    held = dict(params, gate_up=params["gate_up"][6:], down=params["down"][6:])
    one = layer(dataclasses.replace(cfg, experts_held=2, expert_share=3)
                ).apply({"params": held}, x)
    want, _ = window_moe_ref._routed(
        x, held, dict(uncut, experts_held=2, expert_share=3), None)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the registry

def test_the_registry_entry_is_the_published_model():
    """Laguna-XS.2's published defaults build 33.44 B parameters from
    shapes alone (33.43 B of the published keys plus 3 M of per-head
    gates); the pattern is data."""
    published = WINDOW_MOE_CONFIGS["laguna-xs2"]
    layers = published.layers
    assert len(layers) == 40
    assert layers[0] == window_moe.Layer(FULL, 48, "dense")
    assert layers[1:5] == (window_moe.Layer(SLIDING, 64, "sparse"),) * 3 + (
        window_moe.Layer(FULL, 48, "sparse"),)
    assert sum(layer.attention == FULL for layer in layers) == 10
    model = get_model("laguna-xs2").make_model()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == pytest.approx(33.44e9, rel=5e-4)
    gates = 2048 * sum(layer.heads for layer in layers)
    assert count - gates == pytest.approx(33.43e9, rel=5e-4)
    assert gates == 2048 * (10 * 48 + 30 * 64)     # 4.9 M: "33.4B" either way


@pytest.mark.parametrize("broken", [
    {"num_attention_heads_per_layer": (4, 5, 4)},     # 5 heads over 2
    {"mlp_layer_types": ("sparse", "dense", "sparse")},
    {"first_k_dense_replace": 2},
    {"num_hidden_layers": 4},                          # lists hold three
    {"experts_held": 3},
    {"gating": False},
])
def test_a_configuration_that_contradicts_itself_is_refused(broken):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **broken)


def test_attention_key_tiles_gauge_is_set_where_the_call_is_traced():
    """At 1100 positions (three 512-wide key tiles) a window of 8 keeps
    a query tile to its diagonal tile and the one before; the full
    layers sweep everything under the diagonal."""
    from horovod_tpu.common import telemetry

    model = _model(attn_impl="flash")
    ids = jnp.zeros((1, 1100), jnp.int32)
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    found = {(kind, what): telemetry.gauge(
        "horovod_attention_key_tiles",
        labels={"kind": kind, "what": what}).value
        for kind in ("window", "full") for what in ("visited",
                                                     "under_diagonal")}
    assert found == {("window", "visited"): 5, ("window", "under_diagonal"): 6,
                     ("full", "visited"): 6, ("full", "under_diagonal"): 6}


def test_attention_scores_gauge_is_set_where_the_call_is_traced():
    """At 1100 positions and a window of 8 the second and third query
    tiles compute 256 keys a query (two sub-blocks of 128), the first
    its diagonal tile; the full layers compute whole tiles under the
    diagonal. Visible: 8 keys a query but the first 7, and a triangle."""
    from horovod_tpu.common import telemetry

    model = _model(attn_impl="flash")
    ids = jnp.zeros((1, 1100), jnp.int32)
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    found = {(kind, what): telemetry.gauge(
        "horovod_attention_scores",
        labels={"kind": kind, "what": what}).value
        for kind in ("window", "full") for what in ("computed", "visible")}
    assert found == {
        ("window", "computed"): 512 * 512 + 2 * 512 * 256,
        ("window", "visible"): 36 + 1092 * 8,
        ("full", "computed"): 6 * 512 * 512,
        ("full", "visible"): 1100 * 1101 // 2}
