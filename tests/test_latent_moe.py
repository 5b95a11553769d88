"""The latent-attention mixture-of-experts model (models/latent_moe.py,
JoyAI-LLM-Flash's block) at a tiny size on the CPU: the program against
the benchmark's plain float32 reference, the shares of the routed
experts adding up to the uncut layer, no token dropped under any
routing, the rotary positions against their formula, the attention
kernel with query/key heads wider than value heads, the grouped
product, the kernels that move a routed layer's rows against the plain
formulas they replaced, what they cost the host to trace and lower, the
step's second loss term and the scopes in the lowered step."""
import collections
import dataclasses
import functools
import hashlib
import json
import pathlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import correct
from benchmark.reference import latent_moe_ref as ref
from benchmark.trainers import gspmd_mtp
from horovod_tpu.common import telemetry, tracing
from horovod_tpu.models import LATENT_MOE_CONFIGS, get_model, latent_moe
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import routed_rows
from horovod_tpu.ops.grouped_matmul import grouped_matmul
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.ring import dense_attention
from horovod_tpu.parallel.train import (
    AUX_COLLECTION, lm_loss, make_train_step, mtp_loss)

ROOT = pathlib.Path(__file__).resolve().parents[1]
SEQ = 64
TINY = LATENT_MOE_CONFIGS["latent-moe-tiny"]
LEAVES = {"embedding": ["embed", "embedding"],
          "layer_1.kv_b": ["layer_1", "attn", "kv_b", "kernel"],
          "layer_1.gate_up": ["layer_1", "moe", "gate_up"],
          "layer_1.router": ["layer_1", "moe", "router"],
          "mtp.proj": ["mtp", "proj", "kernel"],
          "final_norm.scale": ["final_norm", "scale"]}


def _model(**kw):
    return get_model("latent-moe-tiny").make_model(**kw)


def _params(model, seed=1):
    """Seeded weights, with the norms' scales and everything else moved
    off their initial values (a scale of exactly 1 hides a missing one)."""
    params = nn.unbox(model.init(jax.random.PRNGKey(seed),
                                 np.zeros((1, SEQ), np.int32)))["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


def _dims(model) -> dict:
    return dataclasses.asdict(model.cfg)


# ------------------------------------------------- program and reference

@pytest.mark.parametrize("case", ["f32", "bf16", "bf16-share-flash-remat"])
def test_program_agrees_with_the_plain_reference(case):
    """Logits at the full length and the gradient of the two-term
    objective (next token + 0.3 x the token after), through the
    benchmark's own comparison: tightly in float32, at the harness's
    tolerances in bfloat16; whole, and as share 1 of 4 through the
    attention kernel with every block recomputed."""
    kw = {"f32": {"dtype": jnp.float32},
          "bf16": {},
          "bf16-share-flash-remat": {
              "experts_held": 2, "expert_share": 1, "attn_impl": "flash",
              "remat": True}}[case]
    model = _model(**kw)
    errors = correct.measure_against_reference(
        gspmd_mtp.objective(model), ref, _params(model), _dims(model), SEQ,
        3, LEAVES)
    assert set(errors) == {"logits", "choice_slack", "grad_norm"} | {
        f"grad.{name}" for name in LEAVES}
    if case == "f32":
        assert max(errors.values()) < 1e-4, errors
        assert errors["choice_slack"] == 0.0
    else:
        assert correct.beyond_tolerance(errors) == {}, errors


def test_the_module_runs_only_where_its_collection_is_mutable():
    """`model.apply(variables, ids)` is the main model alone; with the
    collection mutable the module's logits come back beside it, and
    the main logits are the same."""
    model = _model(dtype=jnp.float32)
    params = _params(model)
    ids = np.arange(2 * SEQ, dtype=np.int32).reshape(2, SEQ) % 256
    alone = model.apply({"params": params}, ids)
    both, sown = model.apply({"params": params}, ids,
                             mutable=[AUX_COLLECTION])
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(both))
    (mtp_logits,), = sown[AUX_COLLECTION].values()
    assert mtp_logits.shape == alone.shape
    assert not np.allclose(np.asarray(mtp_logits), np.asarray(alone))
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, num_nextn_predict_layers=2)


# -------------------------------------------------------------- the shares

def _routed_layer(cfg):
    return latent_moe.RoutedExperts(cfg)


def _layer_params(seed=0, dtype=jnp.float32):
    cfg = dataclasses.replace(TINY, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 32, cfg.hidden_size),
                          dtype)
    params = nn.unbox(_routed_layer(cfg).init(jax.random.PRNGKey(seed + 1),
                                              x))["params"]
    params = jax.tree.map(
        lambda a: a * 5 if a.ndim == 2 and a.shape[-1] == 8 else a, params)
    return cfg, x, params


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each, the shared expert counted once:
    the routed parts of all shares plus the shared expert equal what the
    uncut reference gives for the whole layer."""
    cfg, x, params = _layer_params()
    whole, _ = ref._routed(x, params, _dims(_model()), None)
    parts = jnp.zeros_like(x)
    for share in range(4):
        part = dataclasses.replace(cfg, experts_held=2, expert_share=share,
                                   n_shared_experts=0)
        held = {"router": params["router"],
                "gate_up": params["gate_up"][2 * share:2 * share + 2],
                "down": params["down"][2 * share:2 * share + 2]}
        parts = parts + _routed_layer(part).apply({"params": held}, x)
    shared = params["shared"]
    parts = parts + ref._mlp(x, shared["gate"]["kernel"],
                             shared["up"]["kernel"], shared["down"]["kernel"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    # And one share alone is what the reference gives for that share.
    dims = dict(_dims(_model()), experts_held=2, expert_share=3)
    held = dict(params, gate_up=params["gate_up"][6:], down=params["down"][6:])
    one = _routed_layer(dataclasses.replace(
        cfg, experts_held=2, expert_share=3)).apply({"params": held}, x)
    want, _ = ref._routed(x, held, dims, None)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("skew", ["all-held", "none-held", "one-expert",
                                  "one-row-short-of-a-tile"])
def test_no_token_is_dropped_under_skew(skew):
    """A router forced so that every token chooses only experts held
    here (every row of the dispatch buffer holds a pair), so that none
    does (no row does), so that every token sends one choice to the
    same held expert, and so that the rows held end one short of a row
    tile of the kernels (128 tokens, all but one choosing held experts
    only): values and gradients are the reference's plain loop, which
    multiplies every token by every expert."""
    cfg, x, params = _layer_params()
    cfg = dataclasses.replace(cfg, experts_held=2, expert_share=1)
    x = x.at[..., 0].set(4.0)              # a component the router can key on
    router = jnp.zeros_like(params["router"])
    favoured = {"all-held": [2, 3], "none-held": [0, 7],
                "one-expert": [3, 6],
                "one-row-short-of-a-tile": [2, 3]}[skew]
    router = router.at[0, jnp.asarray(favoured)].set(3.0)
    if skew == "one-row-short-of-a-tile":
        # One token keys on another component, for experts 3 and 6.
        x = jnp.concatenate([x, x[::-1]]).at[..., 1].set(0.0)
        x = x.at[1, 5, :2].set(jnp.asarray([0.0, 4.0]))
        router = router.at[1, jnp.asarray([3, 6])].set(3.0)
        assert routed_rows.ROW_TILE == 2 * x.shape[0] * x.shape[1]
    router = router + 0.01 * params["router"]
    held = dict(params, router=router, gate_up=params["gate_up"][2:4],
                down=params["down"][2:4])
    dims = dict(_dims(_model()), experts_held=2, expert_share=1)
    assert latent_moe.buffer_rows(64, cfg) == (32, 128)

    def program(p, x):
        out, sown = _routed_layer(cfg).apply({"params": p}, x,
                                             mutable=["choices"])
        return jnp.sum(jnp.sin(out)), sown["choices"]["routed"][0]

    def reference(p, x):
        return jnp.sum(jnp.sin(ref._routed(x, p, dims, None)[0]))

    (got, chosen), got_grads = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(held, x)
    want, want_grads = jax.value_and_grad(reference, argnums=(0, 1))(held, x)
    here = int(jnp.sum((chosen >= 2) & (chosen < 4)))
    assert here == {"all-held": 128, "none-held": 0, "one-expert": 64,
                    "one-row-short-of-a-tile": 255}[skew]
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def test_the_layer_reports_what_it_was_built_with():
    cfg = dataclasses.replace(TINY, experts_held=2, expert_share=1)
    x = jnp.zeros((2, 32, cfg.hidden_size), cfg.dtype)
    jax.eval_shape(lambda: _routed_layer(cfg).init(jax.random.PRNGKey(0), x))
    labels = {"experts_held": "2", "shares": "4"}
    rows = {kind: telemetry.gauge("horovod_moe_dispatch_rows",
                                  labels={**labels, "kind": kind}).value
            for kind in ("expected", "buffer", "tile")}
    assert rows == {"expected": 32, "buffer": 128,
                    "tile": routed_rows.ROW_TILE}
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, experts_held=3)
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, experts_held=2, expert_share=4)


# ------------------------------------------ the kernels that move the rows

ROUTED_HELD, ROUTED_K, ROUTED_T, ROUTED_D, ROUTED_F = 4, 2, 512, 128, 64
ROUTINGS = ["even", "all-held", "none-held", "one-expert", "ragged",
            "several-a-token"]


def _routing(case: str):
    """512 tokens (two blocks of the token-major kernel) x 2 choices
    among 8 experts, the first 4 held (four row tiles): `even` random
    distinct choices; every pair held (the buffer full); none; every
    token's first choice the same held expert; group sizes 3, 17, 250
    and 1, no multiple of a row tile or a chunk, their tokens anywhere;
    a third of the tokens with both choices held and the rest with
    none."""
    rng = np.random.default_rng(5)
    T, held = ROUTED_T, ROUTED_HELD
    if case == "even":
        chosen = np.argsort(rng.random((T, 2 * held)), axis=1)[:, :ROUTED_K]
    elif case == "all-held":
        chosen = np.argsort(rng.random((T, held)), axis=1)[:, :ROUTED_K]
    elif case == "none-held":
        chosen = held + np.argsort(rng.random((T, held)),
                                   axis=1)[:, :ROUTED_K]
    elif case == "one-expert":
        chosen = np.stack([np.full(T, 2), rng.integers(held, 2 * held, T)], 1)
    elif case == "ragged":
        first = np.repeat([0, 1, 2, 3, 5], [3, 17, 250, 1, T - 271])
        chosen = rng.permutation(np.stack([first, np.full(T, 6)], 1))
    else:
        both = rng.random(T) < 1 / 3
        chosen = np.where(both[:, None], rng.permuted(
            np.tile([[0, 1], [2, 3]], (T // 2, 1)), axis=1), [[4, 7]])
    key = np.minimum(chosen, held).reshape(-1).astype(np.int32)
    gates = jnp.asarray(rng.random((T, ROUTED_K)), jnp.float32)
    routing = routed_rows.route(jnp.asarray(key), gates, held)
    held_rows = int(np.sum(key < held))
    assert int(routing.held_rows[0]) == held_rows
    np.testing.assert_array_equal(np.asarray(routing.sizes),
                                  np.bincount(key, minlength=held + 1)[:held])
    if case == "several-a-token":
        assert 0 < held_rows == 2 * int(np.sum(chosen[:, 0] < held))
    return key, gates, routing, held_rows


def _poisoned(x, held_rows):
    """`x` with every row behind `held_rows` NaN: whatever reads one as
    a number shows."""
    return x.at[held_rows:].set(jnp.nan)


# What the kernels replaced (models/latent_moe.py before ops/routed_rows.py),
# on a buffer of T k rows: gathers at the size of the buffer. `order` is
# the pair at each row, `place` its inverse, `here` whether a pair is held.

def _plain_held_rows(y, place, here):
    rows = jnp.where(here[:, None], y[place], 0).astype(jnp.float32)
    return rows.reshape(-1, ROUTED_K, y.shape[-1])


def _plain_dispatch(tokens, order):
    return tokens[order // ROUTED_K]


def _plain_dispatch_bwd(g, place, here):
    return _plain_held_rows(g, place, here).sum(1).astype(g.dtype)


def _plain_combine(y, gates, place, here):
    return jnp.einsum("tk,tkd->td", gates, _plain_held_rows(y, place, here))


def _plain_combine_bwd(y, gates, order, place, here, g):
    rows = _plain_held_rows(y, place, here).astype(y.dtype)
    d_y = (g.astype(y.dtype)[order // ROUTED_K]
           * gates.reshape(-1)[order][:, None].astype(y.dtype))
    d_gates = jnp.einsum("tkd,td->tk", rows, g,
                         preferred_element_type=jnp.float32)
    return d_y, d_gates


def _plain_activation(h):
    f = h.shape[1] // 2
    return nn.silu(h[:, :f]) * h[:, f:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ROUTINGS)
def test_the_kernels_equal_the_formulas_they_replaced(case, dtype):
    """`dispatch`, `gated_activation` and `combine`, forward and every
    gradient (tokens, `h`, `y`, gates), on the rows that hold a pair,
    with what comes in NaN behind them: the plain gathers' values, equal
    where the arithmetic is the same (moved rows, gate x gradient) and to
    float32 rounding where a sum runs in another order."""
    key, gates, routing, held_rows = _routing(case)
    pairs, rows = len(key), routed_rows.padded_rows(len(key))
    rng = np.random.default_rng(1)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    order = np.argsort(key, kind="stable")
    place, here = np.argsort(order), key < ROUTED_HELD
    as_f32 = lambda x: np.asarray(x, np.float32)
    exact = dict(rtol=0, atol=0)
    summed = dict(rtol=1e-5, atol=1e-5)

    # Into the buffer, and its transpose.
    tokens = normal(ROUTED_T, ROUTED_D).astype(dtype)
    buffer, pull = jax.vjp(lambda t: routed_rows.dispatch(t, routing), tokens)
    assert buffer.shape == (rows, ROUTED_D) and buffer.dtype == dtype
    np.testing.assert_allclose(
        as_f32(buffer[:held_rows]),
        as_f32(_plain_dispatch(tokens, order)[:held_rows]), **exact)
    g = _poisoned(normal(rows, ROUTED_D).astype(dtype), held_rows)
    d_tokens, = pull(g)
    assert d_tokens.dtype == dtype
    np.testing.assert_allclose(
        as_f32(d_tokens), as_f32(_plain_dispatch_bwd(g[:pairs], place, here)),
        **(summed if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)))

    # Between the grouped products.
    h = normal(rows, 2 * ROUTED_F).astype(dtype)
    act, pull = jax.vjp(lambda h: routed_rows.gated_activation(
        _poisoned(h, held_rows), routing.held_rows), h)
    want, plain_pull = jax.vjp(
        lambda h: _plain_activation(h.astype(jnp.float32)), h)
    rounded = (summed if dtype == jnp.float32
               else dict(rtol=1e-2, atol=1e-2))
    np.testing.assert_allclose(as_f32(act[:held_rows]),
                               as_f32(want[:held_rows]), **rounded)
    g = normal(rows, ROUTED_F).astype(dtype)
    d_h, = pull(_poisoned(g, held_rows))
    assert d_h.dtype == dtype
    np.testing.assert_allclose(
        as_f32(d_h[:held_rows]),
        as_f32(plain_pull(g.astype(jnp.float32))[0][:held_rows]), **rounded)

    # Back to the tokens, and the rows' and the gates' gradients.
    y = _poisoned(normal(rows, ROUTED_D).astype(dtype), held_rows)
    out, pull = jax.vjp(lambda y, gates: routed_rows.combine(
        y, gates, routing), y, gates)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_plain_combine(y[:pairs], gates, place,
                                                   here)), **summed)
    g = normal(ROUTED_T, ROUTED_D)
    d_y, d_gates = pull(g)
    want_y, want_gates = _plain_combine_bwd(y[:pairs], gates, order, place,
                                            here, g)
    assert d_y.dtype == dtype and d_gates.dtype == jnp.float32
    np.testing.assert_allclose(as_f32(d_y[:held_rows]),
                               as_f32(want_y[:held_rows]), **exact)
    np.testing.assert_allclose(np.asarray(d_gates), np.asarray(want_gates),
                               rtol=1e-4, atol=1e-4)


def test_a_row_of_18_lane_tiles_moves_whole():
    """A width of 2304 (Kimi-Linear's hidden size: 18 lane tiles, landed
    as 24 for the chip's DMA of a row, `routed_rows._landing`): into the
    buffer, back to the tokens, and the gradients of both, the plain
    gathers' values on the rows that hold a pair."""
    width = 18 * routed_rows.LANES
    assert routed_rows._landing(width) == (24, routed_rows.LANES)
    assert routed_rows._landing(2048) == (16, routed_rows.LANES)
    key, gates, routing, held_rows = _routing("ragged")
    pairs, rows = len(key), routed_rows.padded_rows(len(key))
    order = np.argsort(key, kind="stable")
    place, here = np.argsort(order), key < ROUTED_HELD
    rng = np.random.default_rng(2)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.bfloat16)
    tokens = normal(ROUTED_T, width)
    buffer, pull = jax.vjp(lambda t: routed_rows.dispatch(t, routing), tokens)
    np.testing.assert_array_equal(
        np.asarray(buffer[:held_rows], np.float32),
        np.asarray(_plain_dispatch(tokens, order)[:held_rows], np.float32))
    y = _poisoned(normal(rows, width), held_rows)
    out, pull = jax.vjp(lambda y, gates: routed_rows.combine(
        y, gates, routing), y, gates)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_plain_combine(y[:pairs], gates, place,
                                                   here)), rtol=1e-5,
        atol=1e-5)
    g = jnp.asarray(rng.standard_normal((ROUTED_T, width)), jnp.float32)
    d_y, d_gates = pull(g)
    want_y, want_gates = _plain_combine_bwd(y[:pairs], gates, order, place,
                                            here, g)
    np.testing.assert_array_equal(np.asarray(d_y[:held_rows], np.float32),
                                  np.asarray(want_y[:held_rows], np.float32))
    np.testing.assert_allclose(np.asarray(d_gates), np.asarray(want_gates),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", ROUTINGS)
def test_the_layer_reads_no_row_behind_the_pairs(case):
    """`held_experts`' chain with each buffer between its kernels
    poisoned behind the rows that hold a pair (the dispatch buffer, `h`,
    the activation, `y`, and on the way back their gradients): values
    and gradients are finite and those of plain indexing over every
    pair."""
    key, gates, routing, held_rows = _routing(case)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.standard_normal((ROUTED_T, ROUTED_D)),
                         jnp.float32)
    w_in = jnp.asarray(rng.standard_normal(
        (ROUTED_HELD, ROUTED_D, 2 * ROUTED_F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal(
        (ROUTED_HELD, ROUTED_F, ROUTED_D)) * 0.1, jnp.float32)

    @jax.custom_vjp
    def poison(x):
        return _poisoned(x, held_rows)

    poison.defvjp(lambda x: (poison(x), None),
                  lambda _, g: (_poisoned(g, held_rows),))

    def kernels(tokens, gates, w_in, w_out):
        buffer = poison(routed_rows.dispatch(tokens, routing))
        h = poison(grouped_matmul(buffer, w_in, routing.sizes))
        h = poison(routed_rows.gated_activation(h, routing.held_rows))
        y = poison(grouped_matmul(h, w_out, routing.sizes))
        return jnp.sum(jnp.sin(routed_rows.combine(y, gates, routing)))

    def plain(tokens, gates, w_in, w_out):
        expert = jnp.minimum(key, ROUTED_HELD - 1)
        x = tokens[jnp.arange(len(key)) // ROUTED_K]
        h = jnp.einsum("pd,pdf->pf", x, w_in[expert])
        y = jnp.einsum("pf,pfd->pd", _plain_activation(h), w_out[expert])
        y = jnp.where((key < ROUTED_HELD)[:, None], y, 0)
        return jnp.sum(jnp.sin(jnp.einsum(
            "tk,tkd->td", gates, y.reshape(ROUTED_T, ROUTED_K, ROUTED_D))))

    args = (tokens, gates, w_in, w_out)
    got, got_grads = jax.value_and_grad(kernels, argnums=(0, 1, 2, 3))(*args)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2, 3))(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


# ------------------------------------------------------- rotary positions

def test_rope_turns_adjacent_pairs_as_complex_numbers():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 16, 3, 8)).astype(np.float32)
    positions = np.arange(5, 21)
    theta = 32e6
    got = latent_moe.apply_rope_interleaved(
        jnp.asarray(x), *latent_moe.rope_angles(jnp.asarray(positions), 8,
                                                theta))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    angle = positions[:, None] * theta ** (-np.arange(0, 8, 2) / 8)[None, :]
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_latent_attention_shares_one_rotated_key_slice_among_heads():
    """Against the formula written out head by head with numpy: every
    head's key is [its own k_nope | THE position's rotated k_rope], its
    query [q_nope | its own rotated q_rope], scores over sqrt(192)'s
    counterpart, values 16 wide."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, 12, cfg.hidden_size)).astype(np.float32)
    rope = latent_moe.rope_angles(jnp.arange(12), cfg.qk_rope_head_dim,
                                  cfg.rope_theta)
    module = latent_moe.LatentAttention(cfg)
    params = jax.tree.map(
        lambda a: a * 10, nn.unbox(module.init(jax.random.PRNGKey(0),
                                               jnp.asarray(x), rope)))
    got = np.asarray(module.apply(params, jnp.asarray(x), rope))

    p = jax.tree.map(np.asarray, params["params"])
    nope, rot, rank = 16, 8, cfg.kv_lora_rank

    def norm(v, scale):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-6) * scale

    def turn(v):                                           # (S, rot)
        z = v[:, 0::2] + 1j * v[:, 1::2]
        angle = np.arange(12)[:, None] * cfg.rope_theta ** (
            -np.arange(0, rot, 2) / rot)[None, :]
        z = z * np.exp(1j * angle)
        return np.stack([z.real, z.imag], -1).reshape(v.shape)

    c_q = norm(x[0] @ p["q_a"]["kernel"], p["q_a_norm"]["scale"])
    kv_a = x[0] @ p["kv_a"]["kernel"]
    c_kv = norm(kv_a[:, :rank], p["kv_a_norm"]["scale"])
    k_rope = turn(kv_a[:, rank:])
    out = np.zeros((12, cfg.hidden_size), np.float32)
    mask = np.tril(np.ones((12, 12), bool))
    for h in range(cfg.num_attention_heads):
        q = c_q @ p["q_b"]["kernel"][:, h]
        kv = c_kv @ p["kv_b"]["kernel"][:, h]
        q = np.concatenate([q[:, :nope], turn(q[:, nope:])], -1)
        k = np.concatenate([kv[:, :nope], k_rope], -1)
        scores = np.where(mask, q @ k.T / np.sqrt(nope + rot), -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        out += probs @ kv[:, nope:] @ p["o"]["kernel"][h]
    np.testing.assert_allclose(got[0], out, rtol=2e-4, atol=2e-4)


# ----------------------------------------- the kernel with two head sizes

def _qkv(dqk, dv, S=96, B=2, H=2, seed=0):
    rng = np.random.default_rng(seed)
    make = lambda d: jnp.asarray(
        rng.standard_normal((B, S, H, d)).astype(np.float32))
    return make(dqk), make(dqk), make(dv)


@pytest.mark.parametrize("dqk,dv", [(192, 128), (24, 16), (16, 24)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_two_head_sizes_matches_dense(dqk, dv, causal):
    q, k, v = _qkv(dqk, dv)

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    flash = lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=32, interpret=True)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=causal)
    got = flash(q, k, v)
    assert got.shape == (2, 96, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got_grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want_grads = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(got_grads, want_grads, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4)


def test_equal_head_sizes_get_the_call_they_always_had():
    """Heads within one lane tile (every model before the latent one)
    add no keyword to the kernels' `pallas_call`: no compiler
    parameters in the traced program. Wider heads raise the VMEM
    limit."""
    assert fa._compiler_params(64, 64) == {}
    assert fa._compiler_params(128, 128) == {}
    wide = fa._compiler_params(192, 128)["compiler_params"]
    assert wide.vmem_limit_bytes == fa.WIDE_HEAD_VMEM_BYTES

    def text(dqk, dv):
        q, k, v = _qkv(dqk, dv, S=64)
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, interpret=False))))(q, k, v))

    assert "compiler_params=FrozenDict({})" in text(64, 64)
    assert "vmem_limit_bytes" not in text(64, 64)
    assert f"vmem_limit_bytes={fa.WIDE_HEAD_VMEM_BYTES}" in text(192, 128)


# ------------------------------------------------------ the grouped product

@pytest.mark.parametrize("sizes", [[10, 0, 17, 5], [0, 0, 0, 0],
                                   [16, 16, 16, 16], [0, 64, 0, 0]])
def test_grouped_matmul_equals_a_loop_over_groups(sizes):
    """Forward and backward on the rows of the groups; an empty group
    costs nothing and gets a zero gradient. The rows of no group (behind
    the last) are nobody's: nothing is asserted of them."""
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 32, 48)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    rows = sum(sizes)

    def loop(lhs, rhs):
        out, start = jnp.zeros((64, 48)), 0
        for g, n in enumerate(sizes):
            out = out.at[start:start + n].set(lhs[start:start + n] @ rhs[g])
            start += n
        return out[:rows]

    def grouped(lhs, rhs):
        return grouped_matmul(lhs, rhs, group_sizes)[:rows]

    np.testing.assert_allclose(np.asarray(grouped(lhs, rhs)),
                               np.asarray(loop(lhs, rhs)),
                               rtol=1e-5, atol=1e-5)
    grads = jax.grad(lambda l, r: jnp.sum(jnp.sin(grouped(l, r))),
                     argnums=(0, 1))(lhs, rhs)
    wants = jax.grad(lambda l, r: jnp.sum(jnp.sin(loop(l, r))),
                     argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(grads[0][:rows]),
                               np.asarray(wants[0][:rows]), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(grads[1]), np.asarray(wants[1]),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------- the step

def _step(model, **kw):
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    ids = np.random.default_rng(0).integers(0, 256, (2, SEQ), dtype=np.int32)
    init, step, _ = make_train_step(
        model, optax.adamw(1e-3), lm_loss, mesh=mesh, donate=False, **kw)(
            jax.random.PRNGKey(0), ids)
    return init, step, ids


def test_the_step_differentiates_both_terms():
    """The step's loss is the objective the comparison is given, its
    second term the module's; without `aux_loss_fn` the step trains the
    first term alone and the module's own leaves get no gradient."""
    model = _model()
    init, step, ids = _step(model, aux_loss_fn=mtp_loss(0.3))
    state = init(jax.random.PRNGKey(0))
    value, _, _ = gspmd_mtp.objective(model)(state.params, ids, SEQ)
    new_state, loss = step(state, ids)
    assert float(loss) == pytest.approx(float(value), rel=1e-3)
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), state.params,
                         new_state.params)
    assert all(jax.tree.leaves(moved))

    _, plain, _ = _step(model)
    after, first_term = plain(state, ids)
    assert float(first_term) == pytest.approx(
        float(lm_loss(model.apply({"params": state.params}, ids), ids)),
        rel=1e-3)
    assert float(first_term) < float(loss)
    # (AdamW's weight decay alone: 1e-7 of the weight; a gradient moves
    # every entry by the learning rate, 1e-3.)
    np.testing.assert_allclose(
        np.asarray(after.params["mtp"]["proj"]["kernel"]),
        np.asarray(state.params["mtp"]["proj"]["kernel"]), rtol=1e-5,
        atol=1e-8)
    assert float(jnp.max(jnp.abs(
        new_state.params["mtp"]["proj"]["kernel"]
        - state.params["mtp"]["proj"]["kernel"]))) > 5e-4
    losses = [float(loss)]
    for _ in range(4):
        new_state, loss = step(new_state, ids)
        losses.append(float(loss))
    assert losses == sorted(losses, reverse=True)


def test_the_lowered_step_names_the_new_regions():
    """Forward and backward: the latent attention's own work, routing,
    the experts, and the module (outermost, over its block's scopes and
    the shared head)."""
    init, step, ids = _step(_model(experts_held=2, attn_impl="flash",
                                   remat=True),
                            aux_loss_fn=mtp_loss(0.3))
    text = step.__wrapped__.lower(init(jax.random.PRNGKey(0)), ids).as_text(
        debug_info=True)
    scopes = (tracing.SCOPE_ATTN_LATENT, tracing.SCOPE_MOE_ROUTE,
              tracing.SCOPE_MOE_EXPERTS, tracing.SCOPE_MTP)
    assert len(set(scopes)) == 4 and all(s.startswith("hvd.") for s in scopes)
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    assert f"{tracing.SCOPE_MTP}/mtp/block/attn/{tracing.SCOPE_ATTN_LATENT}" \
        in text
    assert f"{tracing.SCOPE_MTP}/lm_head" in text
    assert "transpose(jvp(LatentMoELM))" in text
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert kernel in text
    # The rows move in kernels behind jits of their own, called under
    # the layer's scopes forward, in the recomputed forward and backward;
    # nothing under the routing scope gathers at the size of the dispatch
    # buffer (2 x 64 tokens x 2 choices, 64 wide).
    route, experts = tracing.SCOPE_MOE_ROUTE, tracing.SCOPE_MOE_EXPERTS
    calls = set(re.findall(r'loc\("([^"]*)/jit\((_\w+)\)"', text))
    for scope, entry, passes in (
            (route, "_route", ("jvp", "rematted_computation")),
            (route, "_gather_rows", ("jvp", "rematted_computation")),
            (route, "_sum_rows", ("jvp", "transpose")),
            (route, "_combine_bwd_rows", ("transpose",)),
            (experts, "_silu_gate", ("jvp", "rematted_computation")),
            (experts, "_silu_gate_bwd", ("transpose",))):
        stacks = [stack for stack, name in calls if name == entry]
        assert stacks and all(stack.endswith(f"/moe/{scope}")
                              for stack in stacks), (entry, stacks)
        for part in passes:
            assert any(part in stack for stack in stacks), (entry, part)
    pairs = 2 * SEQ * TINY.num_experts_per_tok
    sized = f"({pairs}|{routed_rows.padded_rows(pairs)})x{TINY.hidden_size}x"
    wide = [line for line in text.splitlines()
            if "stablehlo.gather" in line and route in line
            and re.search(rf"-> tensor<{sized}", line)]
    assert wide == []


KERNEL_BODIES = ("_gather_kernel", "_sum_kernel", "_combine_bwd_kernel",
                 "_silu_gate_kernel", "_silu_gate_bwd_kernel")


def test_a_kernel_is_traced_and_lowered_once_however_many_layers_call_it(
        monkeypatch):
    """What the routed layer's kernels cost the host, by counts.
    Building and lowering the step of a model with two applications of
    the layer (one routed layer and the module's block; `remat`) traces
    each kernel body a fixed number of times: once per branch of the
    platform rule, per output dtype and per tracing context (jax keys a
    jit's trace by the mesh in scope, and `init`, the step and the
    derivative rules are traced under three). A model with four
    applications, built afterwards, traces none again: a module-level
    jit keeps its trace for the life of the process. The step lowered
    for the TPU holds each kernel's body once for the forward pass and,
    where a block recomputes it, once for the recomputation (jax's
    partial evaluation copies a jit it only evaluates), whatever the
    number of layers. The widths, 96 and 48, are no other test's:
    nothing here is in the caches beforehand."""
    traced = collections.Counter()

    def counting(name, body):
        @functools.wraps(body)
        def counted(*args, **kw):
            traced[name] += 1
            return body(*args, **kw)
        return counted

    for name in KERNEL_BODIES:
        monkeypatch.setattr(routed_rows, name,
                            counting(name, getattr(routed_rows, name)))

    def lowered_kernels(layers: int):
        model = _model(experts_held=2, remat=True, num_hidden_layers=layers,
                       hidden_size=96, moe_intermediate_size=48)
        mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
        ids = np.zeros((2, 40), np.int32)
        init, step, _ = make_train_step(
            model, optax.adamw(1e-3), lm_loss, mesh=mesh, donate=False,
            aux_loss_fn=mtp_loss(0.3))(jax.random.PRNGKey(0), ids)
        text = step.__wrapped__.trace(
            init(jax.random.PRNGKey(0)), ids).lower(
                lowering_platforms=("tpu",)).as_text()
        found = collections.Counter(
            re.findall(r'kernel_name = "(routed_\w+)"', text))
        calls = collections.Counter(
            re.findall(r"call @(_[a-z_]+?)(?:_\d+)?\(", text))
        return found, calls

    once = {"routed_gather_rows": 2, "routed_gated_activation": 2,
            "routed_sum_rows": 2,       # float32 out, and the model's dtype
            "routed_combine_bwd_rows": 1, "routed_gated_activation_bwd": 1}
    two, calls = lowered_kernels(2)
    first = dict(traced)
    assert set(first) == set(KERNEL_BODIES)
    branches, contexts = 2, 3
    for name, count in first.items():
        dtypes = 2 if name == "_sum_kernel" else 1
        assert count % branches == 0, first
        assert count <= branches * contexts * dtypes, first
    assert two == once
    assert calls["_gather_rows"] == 4 and calls["_sum_rows"] == 4
    four, calls = lowered_kernels(4)
    assert dict(traced) == first
    assert four == once
    assert calls["_gather_rows"] == 8 and calls["_sum_rows"] == 8


# -------------------------------------------------------------- the registry

def test_the_registry_entry_is_the_published_configuration():
    """Every published key the benchmark's configuration hands to the
    model and did not cut is the registry's default."""
    config = json.loads(
        (ROOT / "benchmark/configs/latent_moe/joyai-llm-flash.json").read_text())
    published = LATENT_MOE_CONFIGS["joyai-llm-flash"]
    for keyword, key in config["model_kwargs"].items():
        if key in config["reduced"]:
            assert config[key] != config["published"][key]
            continue
        assert getattr(published, keyword) == config[key], keyword
    assert published.num_hidden_layers == config["published"][
        "num_hidden_layers"]
    assert published.n_routed_experts == config["published"][
        "n_routed_experts"] == config["n_routed_experts_published"]
    assert published.vocab_size == config["published"]["vocab_size"]
    assert published.qk_head_dim == config["qk_head_dim"] == 192
    assert published.held == 256 and published.expert_share == 0


# ---------------------------------------------- the latent attention's options

# sha256 of the lowered text of the tiny model's recomputed gradient, as
# the tree lowered it before `LatentAttention` took a query projection
# without low rank (`q_lora_rank` None) and `rope` None.
LOWERED_BEFORE_THE_OPTIONS = {
    "dense": "2cac1028bf9bbbfb9732710a73678e21"
             "edb13ab51d529d8d6e63923d1d547e2e",
    "flash": "6d750191627b1272d34778dd265cae48"
             "4a70bc929f76d32b86700927f942b4e6",
}


@pytest.mark.parametrize("impl", sorted(LOWERED_BEFORE_THE_OPTIONS))
def test_the_options_leave_the_low_rank_rotary_attention_as_it_lowered(impl):
    """A configuration with a query low rank and rotary positions (this
    family's) lowers to the text it lowered to before the options."""
    model = _model(attn_impl=impl, remat=True)
    ids = jnp.zeros((1, SEQ), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)["params"])

    def loss(p, ids):
        return jnp.sum(model.apply({"params": p}, ids).astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        LOWERED_BEFORE_THE_OPTIONS[impl]


def test_without_low_rank_or_positions_the_query_is_one_product():
    """`q_lora_rank` None: one projection `q` (no `q_a`, `q_a_norm`,
    `q_b`); `rope` None: the shared key slice is `x W_kva`'s own, the
    same at every position for the same input."""
    cfg = dataclasses.replace(TINY, q_lora_rank=None, dtype=jnp.float32)
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(0), (1, 1, 64)),
                 (1, 8, 1))
    params = nn.unbox(latent_moe.LatentAttention(cfg).init(
        jax.random.PRNGKey(1), x, None))["params"]
    assert set(params) == {"q", "kv_a", "kv_a_norm", "kv_b", "o"}
    assert params["q"]["kernel"].shape == (64, 2, 24)
    out = latent_moe.LatentAttention(cfg).apply({"params": params}, x, None)
    # Identical inputs at every position and no positions: causal
    # attention over equal keys and values gives equal outputs.
    np.testing.assert_allclose(np.asarray(out[0, 1:]),
                               np.asarray(jnp.broadcast_to(out[0, :1],
                                                           (7, 64))),
                               rtol=1e-5, atol=1e-6)
