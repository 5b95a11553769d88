"""The latent-attention mixture-of-experts model (models/latent_moe.py,
JoyAI-LLM-Flash's block) at a tiny size on the CPU: the program against
the benchmark's plain float32 reference, the shares of the routed
experts adding up to the uncut layer, no token dropped under any
routing, the rotary positions against their formula, the attention
kernel with query/key heads wider than value heads, the grouped
product, the step's second loss term and the scopes in the lowered
step."""
import dataclasses
import json
import pathlib

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


@pytest.mark.parametrize("skew", ["all-held", "none-held", "one-expert"])
def test_no_token_is_dropped_under_skew(skew):
    """A router forced so that every token chooses only experts held
    here (every row of the dispatch buffer holds a pair), so that none
    does (no row does), and so that every token sends one choice to the
    same held expert: values and gradients are the reference's, which
    multiplies every token by every expert."""
    cfg, x, params = _layer_params()
    cfg = dataclasses.replace(cfg, experts_held=2, expert_share=1)
    x = x.at[..., 0].set(4.0)              # a component the router can key on
    router = jnp.zeros_like(params["router"])
    favoured = {"all-held": [2, 3], "none-held": [0, 7],
                "one-expert": [3, 6]}[skew]
    router = router.at[0, jnp.asarray(favoured)].set(3.0)
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
    assert here == {"all-held": 128, "none-held": 0, "one-expert": 64}[skew]
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
            for kind in ("expected", "buffer")}
    assert rows == {"expected": 32, "buffer": 128}
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, experts_held=3)
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, experts_held=2, expert_share=4)


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
