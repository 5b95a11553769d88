"""Under jit: the compiled step names its regions (`jax.named_scope`s
`hvd.loss`, `hvd.optimizer`, and in every model family the blocks'
parts: `hvd.mlp`, `hvd.norm`, `hvd.embed`, `hvd.attn.*`) and a step
call records its spans in the XLA profile (`hvd.step` and `wrap_step`'s
parts), docs/tracing.md "Under jit". All on the CPU at a tiny size: the
names in the lowered and compiled program, the program unchanged by
them, the spans read back from a profile of the host."""
import contextlib
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import tracing
from horovod_tpu.models import get_model
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import (
    lm_loss, make_train_step, mtp_loss, softmax_xent)

SEQ, BATCH, VOCAB = 16, 2, 64
IDS = np.arange(BATCH * SEQ, dtype=np.int32).reshape(BATCH, SEQ) % VOCAB


# The model families whose blocks name their parts, each at a tiny size.
FAMILIES = ("gpt2", "bert", "window_moe", "latent_moe")


def _model(family="gpt2"):
    if family in ("gpt2", "bert"):
        return get_model(f"{family}-tiny").make_model(
            vocab_size=VOCAB, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_len=SEQ, attn_impl="dense")
    # The benchmark's cells recompute each block of these.
    name = {"window_moe": "window-moe-tiny", "latent_moe": "latent-moe-tiny"}
    return get_model(name[family]).make_model(remat=True)


def _gspmd_step(family="gpt2"):
    """`make_train_step`'s init and step on one device."""
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    extra = ({"aux_loss_fn": mtp_loss(0.3)} if family == "latent_moe"
             else {})
    init, step, _ = make_train_step(
        _model(family), optax.adamw(1e-3), lm_loss, mesh=mesh, donate=False,
        **extra)(jax.random.PRNGKey(0), IDS)
    return init, step


def _compiled_step(family="gpt2"):
    init, step = _gspmd_step(family)
    state = init(jax.random.PRNGKey(0))
    return jax.jit(step.raw).lower(state, IDS).compile()


def _user_step(model, tx):
    """The README's step: the user's own lines around the wrapper."""
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, batch), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss
    return train_step


def _text(lowered) -> str:
    return lowered.as_text(debug_info=True)


# ------------------------------------------------------------ the scopes

SCOPES = [tracing.SCOPE_LOSS, tracing.SCOPE_OPTIMIZER,
          tracing.SCOPE_ATTN_LATENT, tracing.SCOPE_ATTN_PROJ,
          tracing.SCOPE_ATTN_WINDOW, tracing.SCOPE_ATTN_FULL,
          tracing.SCOPE_MOE_ROUTE, tracing.SCOPE_MOE_EXPERTS,
          tracing.SCOPE_MTP, tracing.SCOPE_MLP, tracing.SCOPE_NORM,
          tracing.SCOPE_EMBED]


def test_the_vocabulary_is_sixteen_names_under_one_prefix():
    spans = [tracing.SPAN_STEP, tracing.SPAN_WRAP_PREPARE,
             tracing.SPAN_WRAP_BUILD, tracing.SPAN_WRAP_CALL]
    names = SCOPES + spans
    assert len(set(names)) == 16
    assert all(name.startswith("hvd.") for name in names)
    # A span of wrap_step's is told from the whole call by its prefix.
    assert all(name.startswith("hvd.wrap_step.") for name in spans[1:])
    # The readers find a scope in a name stack by its text: none may
    # hold another.
    assert not [(a, b) for a in SCOPES for b in SCOPES
                if a != b and a in b]


def test_softmax_xent_lowers_with_the_loss_scope_forward_and_backward():
    logits = jnp.zeros((BATCH, SEQ, VOCAB), jnp.bfloat16)
    text = _text(jax.jit(jax.grad(softmax_xent)).lower(logits, IDS))
    assert f"jvp({tracing.SCOPE_LOSS})" in text
    assert f"transpose(jvp({tracing.SCOPE_LOSS}))" in text
    assert tracing.SCOPE_OPTIMIZER not in text


def test_make_train_step_lowers_with_both_scopes():
    init, step = _gspmd_step()
    state = init(jax.random.PRNGKey(0))
    text = _text(jax.jit(step.raw).lower(state, IDS))
    assert f"jvp({tracing.SCOPE_LOSS})" in text
    assert f"{tracing.SCOPE_OPTIMIZER}/" in text
    # The model's own names are flax's, a role's scope at its call site.
    assert f"jvp(TransformerLM)/stack/layer_0/{tracing.SCOPE_MLP}/mlp" in text
    assert "transpose(jvp(TransformerLM))/stack/layer_0" in text


def test_distributed_optimizer_lowers_the_inner_update_with_the_scope(
        hvd_mesh):
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
    params = {"w": jnp.ones((8, 4))}
    state = tx.init(params)
    text = _text(jax.jit(
        lambda g, s, p: tx.update(g, s, p)).lower(params, state, params))
    assert f"{tracing.SCOPE_OPTIMIZER}/" in text
    assert tracing.SCOPE_LOSS not in text


@pytest.mark.parametrize("which", ["make_train_step", "wrap_step",
                                   "window_moe"])
def test_the_scopes_change_nothing_of_the_program_but_its_metadata(
        which, monkeypatch, hvd_mesh):
    """The compiled step with the scopes and with them patched away:
    the same optimized HLO, instruction for instruction, and the same
    cost by XLA's own analysis (a GPT-2 block, and a window-MoE model
    whose blocks are recomputed)."""
    def compiled():
        if which == "make_train_step":
            return _compiled_step()
        if which == "window_moe":
            return _compiled_step("window_moe")
        model = _model()
        tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), IDS)["params"])
        return jax.jit(_user_step(model, tx)).lower(
            params, jax.eval_shape(tx.init, params), IDS).compile()

    def shape(c):
        # `%name = type opcode(operands), ..., metadata={...}`: the opcode
        # is the last word before the first parenthesis after the type.
        ops = [m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.-]+ = .*?([\w-]+)\(", c.as_text(), re.M)]
        cost = c.cost_analysis()
        return ops, {k: cost[k] for k in ("flops", "bytes accessed")}

    with_scopes = compiled()
    for scope in (tracing.SCOPE_LOSS, tracing.SCOPE_MLP, tracing.SCOPE_NORM,
                  tracing.SCOPE_EMBED, tracing.SCOPE_ATTN_PROJ):
        assert scope in with_scopes.as_text(), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = compiled()
    assert "hvd." not in without.as_text()
    assert shape(with_scopes) == shape(without)
    assert len(shape(with_scopes)[0]) > 50


# ---------------------------------------------- the blocks' parts by role

def _named_ops(compiled) -> set:
    """(opcode, name stack) of every instruction of the optimized HLO,
    fused computations included: what the profile's `tf_op` is made of
    (a nested jit is inlined there, so its ops carry the whole stack)."""
    return {(m.group(1), m.group(2)) for m in re.finditer(
        r"= \S+ ([\w-]+)\(.*?op_name=\"([^\"]*)\"", compiled.as_text())}


def _passes(ops, opcode: str, part: str) -> set:
    """The passes ("forward", "backward") that hold an op `opcode` (any
    where None) whose name stack holds `part`."""
    return {"backward" if "transpose(jvp(" in name else "forward"
            for op, name in ops
            if part in name and "jvp(" in name
            and (opcode is None or op == opcode)}


# (opcode or None, a piece of the name stack) that each family's step
# must hold forward and backward.
_PARTS = {
    "gpt2": [("dot", "/hvd.attn.proj/qkv/"), ("dot", "/hvd.attn.proj/out/"),
             ("dot", "/hvd.attn.full/"), ("dot", "/hvd.mlp/mlp/wi/"),
             ("dot", "/hvd.mlp/mlp/wo/"), (None, "/hvd.norm/ln1/"),
             (None, "/hvd.norm/ln2/"), (None, "/hvd.norm/ln_f/")],
    "window_moe": [("dot", "/hvd.mlp/mlp/gate/"), ("dot", "/hvd.mlp/mlp/up/"),
                   ("dot", "/hvd.mlp/mlp/down/"),
                   (None, "/hvd.norm/attn_norm/"),
                   (None, "/hvd.norm/ffn_norm/"),
                   (None, "/hvd.norm/final_norm/")],
}
_PARTS["bert"] = _PARTS["gpt2"]
_PARTS["latent_moe"] = _PARTS["window_moe"] + [
    (None, "/hvd.mtp/mtp/hvd.norm/norm_h/"),
    (None, "/hvd.mtp/mtp/hvd.norm/norm_e/"),
    (None, "/hvd.mtp/mtp/hvd.norm/final_norm/"),
    (None, "/hvd.mtp/hvd.embed/embed/")]


@pytest.fixture(scope="module")
def family_ops():
    found = {}

    def ops(family):
        if family not in found:
            found[family] = _named_ops(_compiled_step(family))
        return found[family]
    return ops


@pytest.mark.parametrize("family", FAMILIES)
def test_every_family_names_its_blocks_parts_forward_and_backward(
        family, family_ops):
    ops = family_ops(family)
    for opcode, part in _PARTS[family]:
        assert _passes(ops, opcode, part) == {"forward", "backward"}, part
    # The embedding: the gather forward, the scatter into the table
    # backward.
    assert _passes(ops, "gather", f"/{tracing.SCOPE_EMBED}/embed/") == {
        "forward"}
    assert _passes(ops, "scatter", f"/{tracing.SCOPE_EMBED}/embed/") == {
        "backward"}


@pytest.mark.parametrize("family", ["window_moe", "latent_moe"])
def test_a_shared_expert_is_expert_work_and_no_dense_feed_forward(
        family, family_ops):
    shared = [name for _, name in family_ops(family) if "/shared/" in name]
    assert shared
    assert all(tracing.SCOPE_MOE_EXPERTS in name for name in shared)
    assert not [name for name in shared if tracing.SCOPE_MLP in name]
    # Only the leading dense layer's feed-forward is `hvd.mlp`.
    mlp = {re.search(r"/(layer_\d+)/", name).group(1)
           for _, name in family_ops(family) if tracing.SCOPE_MLP in name}
    assert mlp == {"layer_0"}


def test_a_latent_attentions_inner_norms_are_its_own_work(family_ops):
    inner = [name for _, name in family_ops("latent_moe")
             if "_a_norm/" in name]
    assert {"q_a_norm", "kv_a_norm"} <= {
        n for name in inner for n in ("q_a_norm", "kv_a_norm") if n in name}
    assert all(tracing.SCOPE_ATTN_LATENT in name for name in inner)
    assert not [name for name in inner if tracing.SCOPE_NORM in name]


# ------------------------------------------------------------- the spans

def _host_events(trace_dir) -> list:
    """(name, stats, start_ns, end_ns, line) of the profile's `hvd.`
    host events, as `ProfileData` reads the file back."""
    from jax.profiler import ProfileData

    path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found += [(ev.name, dict(ev.stats), ev.start_ns,
                       ev.start_ns + ev.duration_ns, line.name)
                      for ev in line.events if ev.name.startswith("hvd.")]
    return sorted(found, key=lambda e: e[2])


def _steps_of(events) -> list:
    return [int(stats["step"]) for name, stats, *_ in events
            if name == tracing.SPAN_STEP]


def test_make_train_step_calls_are_numbered_step_spans(tmp_path):
    init, step = _gspmd_step()
    state = init(jax.random.PRNGKey(0))
    state, _ = step(state, IDS)          # call 0 compiles, untraced
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            state, loss = step(state, IDS)
        jax.block_until_ready(loss)
    events = _host_events(tmp_path)
    assert {name for name, *_ in events} == {tracing.SPAN_STEP}
    assert _steps_of(events) == [1, 2, 3]


def test_wrap_step_calls_are_step_spans_with_their_parts_inside(
        tmp_path, hvd_mesh):
    model = _model()
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
    wrapped = hvd.wrap_step(_user_step(model, tx),
                            replicated_argnums=(0, 1))
    batch = np.tile(IDS, (hvd.size() // BATCH, 1))
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    opt_state = tx.init(params)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            params, opt_state, loss = wrapped(params, opt_state, batch)
        jax.block_until_ready(loss)
    events = _host_events(tmp_path)
    assert _steps_of(events) == [0, 1, 2]
    names = [name for name, *_ in events]
    # The first call misses the cache and builds; every call prepares
    # and calls, in that order, inside its step span on one thread.
    assert names.count(tracing.SPAN_WRAP_BUILD) == 1
    assert names[:4] == [tracing.SPAN_STEP, tracing.SPAN_WRAP_PREPARE,
                         tracing.SPAN_WRAP_BUILD, tracing.SPAN_WRAP_CALL]
    assert names[4:] == 2 * [tracing.SPAN_STEP, tracing.SPAN_WRAP_PREPARE,
                             tracing.SPAN_WRAP_CALL]
    assert len({line for *_, line in events}) == 1
    steps = [e for e in events if e[0] == tracing.SPAN_STEP]
    for name, _, start, end, _ in events:
        if name != tracing.SPAN_STEP:
            assert sum(s[2] <= start and end <= s[3] for s in steps) == 1


def test_annotations_cost_next_to_nothing_with_no_profile_open():
    import time

    def per_call(n=1000):
        t0 = time.perf_counter()
        for i in range(n):
            with tracing.annotate(tracing.SPAN_STEP, step=i):
                pass
        return (time.perf_counter() - t0) / n

    # A flag test and a Python `with`: about a microsecond, against the
    # 2-20 ms a step call takes. The best of five, and a bound a hundred
    # times that, so that a loaded test machine cannot fail it.
    assert min(per_call() for _ in range(5)) < 100e-6
