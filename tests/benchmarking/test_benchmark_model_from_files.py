"""A model of another kind enters the benchmark by files alone.

The toy below is what the harness must not assume away: it routes every
token to the top k of its experts (a discrete choice, sown into the
`choices` collection), its trainer's objective has a second term, its
attention heads have two sizes, and its configuration names its own
gradient leaves. Its model is registered the way a product model would
be; its reference, trainer, FLOP functions, configuration, traffic and
cell are new files in a copy of `benchmark/` plus index entries, and
nothing that is there is edited. The same toy is then broken, one way a
case, and the comparison has to say so.
"""
import functools
import importlib
import json
import pathlib
import shutil
import sys
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark
from benchmark import (correct, flops, harness, layer_metrics, reference,
                       trainers)
from horovod_tpu.models import registry
from test_benchmark_harness import _run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ = 256


# ----------------------------------------------------------- the program

def _rms(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + 1e-6)).astype(x.dtype)


class ToyRouted(nn.Module):
    """Embedding, a causal running mean, one routed layer (sigmoid
    scores, top k, gates normalised over the chosen), a head. `pick`
    breaks the choice: the k worst experts, one expert fewer, or the
    (k+1)-th best in place of the k-th (the nearest wrong selection)."""
    vocab_size: int
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int
    causal: bool = True
    pick: str = "best"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        e, d, f = self.n_experts, self.d_model, self.d_ff
        x = nn.Embed(self.vocab_size, d, name="embed")(ids)
        count = jnp.arange(1, ids.shape[1] + 1, dtype=jnp.float32)
        x = (x + jnp.cumsum(x, axis=1) / count[None, :, None]).astype(
            self.dtype)
        h = _rms(x)
        scores = jax.nn.sigmoid(nn.Dense(
            e, use_bias=False, name="router", dtype=self.dtype,
            kernel_init=nn.initializers.normal(1.0 / np.sqrt(d)))(h))
        k = self.top_k - (self.pick == "fewer")
        _, chosen = jax.lax.top_k(
            -scores if self.pick == "worst" else scores,
            k + (self.pick == "next"))
        if self.pick == "next":
            chosen = jnp.delete(chosen, k - 1, axis=-1)
        self.sow("choices", "routed", chosen.astype(jnp.int32))
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        init = nn.initializers.normal(1.0 / np.sqrt(d))
        wi = self.param("wi", init, (e, d, f)).astype(self.dtype)
        wo = self.param("wo", init, (e, f, d)).astype(self.dtype)
        up = jax.nn.gelu(jnp.einsum("bsd,bskdf->bskf", h, wi[chosen]))
        down = jnp.einsum("bskf,bskfd->bskd", up, wo[chosen])
        x = x + jnp.einsum("bsk,bskd->bsd", gates, down)
        return nn.Dense(self.vocab_size, use_bias=False, name="head",
                        dtype=self.dtype)(_rms(x))


# ------------------------------------------ the files a later PR would add

REFERENCE = '''
"""The toy's plain float32 reference: decides with the choices it is
given and says how far they lie from its own; chooses itself when it is
given none (what a reference did before the `choices` contract)."""
import jax
import jax.numpy as jnp

from benchmark import correct

SECOND_WEIGHT = 1.0


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)


def _logits(params, ids, dims, choices):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p["embed"]["embedding"][ids]
    count = jnp.arange(1, ids.shape[1] + 1, dtype=jnp.float32)
    x = x + jnp.cumsum(x, axis=1) / count[None, :, None]
    h = _rms(x)
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    sown = jax.tree.leaves(choices)
    if sown:
        chosen, = sown
        slack = correct.choice_slack(scores, chosen, dims["top_k"])
    else:
        chosen, slack = jax.lax.top_k(scores, dims["top_k"])[1], None
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    up = jax.nn.gelu(jnp.einsum("bsd,bskdf->bskf", h, p["wi"][chosen]))
    down = jnp.einsum("bskf,bskfd->bskd", up, p["wo"][chosen])
    x = x + jnp.einsum("bsk,bskd->bsd", gates, down)
    return _rms(x) @ p["head"]["kernel"], slack


def forward(params, ids, dims, choices=None):
    with jax.default_matmul_precision("highest"):
        return _logits(params, ids, dims, choices)


def _xent(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, ids, dims, choices=None, second_weight=SECOND_WEIGHT):
    """The float32 counterpart of trainers/toy_two_terms.py's objective:
    next-token loss plus `second_weight` x the loss of the token after."""
    with jax.default_matmul_precision("highest"):
        logits, _ = _logits(params, ids, dims, choices)
        return (_xent(logits[:, :-1], ids[:, 1:])
                + second_weight * _xent(logits[:, :-2], ids[:, 2:]))
'''

TRAINER = '''
"""A plain jitted AdamW step whose objective has two terms: the
next-token loss and, weighted, the loss of the token after it."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.correct import replica_checksums
from benchmark.trainers import CHOICES, Trainer, optimizer
from horovod_tpu.parallel.train import lm_loss, softmax_xent

SECOND_WEIGHT = 1.0


def build(model, phase, devices, seed):
    tx = optimizer()
    example = np.zeros((phase["batch_per_chip"], phase["seq"]), np.int32)

    def objective(params, ids, n):
        logits, sown = model.apply({"params": params}, ids, mutable=[CHOICES])
        seen, ids_seen = logits[:, :n], ids[:, :n]
        loss = lm_loss(seen, ids_seen) + SECOND_WEIGHT * softmax_xent(
            seen[:, :-2], ids_seen[:, 2:])
        return loss, logits, sown.get(CHOICES, {})

    @jax.jit
    def init(key):
        params = nn.unbox(model.init(key, example))["params"]
        return params, tx.init(params)

    @jax.jit
    def step(state, batch):
        params, opt_state = state
        loss, grads = jax.value_and_grad(
            lambda p: objective(p, batch, batch.shape[1])[0])(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    return Trainer(
        global_batch=example.shape[0],
        init=lambda: init(jax.random.PRNGKey(seed)),
        step=step,
        put=lambda ids: jax.device_put(ids, devices[0]),
        params=lambda state: state[0],
        objective=objective,
        checksums=lambda state: replica_checksums(state[0]),
    )
'''

FLOPS = '''
"""What the toy's step computes, from shapes."""


def matmul_params(dims):
    d = dims["d_model"]
    return (d * dims["n_experts"] + dims["top_k"] * 2 * d * dims["d_ff"]
            + d * dims["vocab_size"])


def routed(dims, seq):
    return 6.0 * matmul_params(dims)
'''

CONFIG = {
    "name": "toy-routed", "source": "toy", "reduced": [],
    "vocab": 256, "width": 32, "experts": 16, "experts_per_token": 3,
    "expert_width": 32,
    "registry": "toy-routed",
    "model_kwargs": {"vocab_size": "vocab", "d_model": "width",
                     "n_experts": "experts", "top_k": "experts_per_token",
                     "d_ff": "expert_width"},
    "model_options": {"causal": True},
    "model_dtypes": {"dtype": "bfloat16"},
    "reference": "toy_routed_ref",
    "flops_per_token": "flops_toy.routed",
    "matmul_params": "flops_toy.matmul_params",
    # A latent attention's heads: queries and keys wider than values.
    "attention": {"heads": 2, "qk_head_dim": 24, "v_head_dim": 16,
                  "calls_per_step": 3},
    "kernels": [],
    "grad_leaves": {"router": ["router", "kernel"], "experts.wi": ["wi"]},
}
FLASH_METRICS = ("flash_attn_ms_per_step", "flash_attention_fwd_roofline",
                 "flash_attention_bwd_roofline")


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """The index of a copy of `benchmark/` with the toy's files and
    entries added; the packages find the copy's new modules by name."""
    added = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", added,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (added / "reference" / "toy_routed_ref.py").write_text(REFERENCE)
    (added / "trainers" / "toy_two_terms.py").write_text(TRAINER)
    (added / "flops_toy.py").write_text(FLOPS)
    (added / "configs" / "toy-routed.json").write_text(json.dumps(CONFIG))
    (added / "traffic" / "toy-s256.json").write_text(json.dumps(
        {"seq": SEQ, "batch_per_chip": 2, "trainer": "toy_two_terms",
         "mesh": {"dp": 1}, "log_every": 2, "pool": 3}))
    (added / "workloads" / "toy-routed-1c.json").write_text(json.dumps(
        # Two terms near ln(256) each, over random logits of deviation 1.
        {"loss_after_20": 12.1, "loss_band": 1.0}))
    index = json.loads((ROOT / "BENCHMARK.json").read_text())
    index["configs"].append(
        {"name": "toy-routed", "source": "toy", "reduced": [], "why": "test",
         "file": "benchmark/configs/toy-routed.json"})
    index["workloads"].append(
        {"name": "toy-routed-1c", "config": "toy-routed", "chips": 1,
         "traffic": "toy-s256", "why": "test"})
    for metric in index["per_layer"]:
        if metric["name"] in FLASH_METRICS:
            metric["workloads"].append("toy-routed-1c")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(index))

    for package, sub in ((benchmark, ""), (reference, "reference"),
                         (trainers, "trainers"),
                         (layer_metrics, "layer_metrics")):
        monkeypatch.setattr(package, "__path__",
                            [*package.__path__, str(added / sub)])
    monkeypatch.setitem(
        registry.REGISTRY, "toy-routed",
        registry.ModelSpec("toy-routed", ToyRouted, None, "lm"))
    yield tmp_path / "BENCHMARK.json"
    for name in ("benchmark.reference.toy_routed_ref",
                 "benchmark.trainers.toy_two_terms", "benchmark.flops_toy"):
        sys.modules.pop(name, None)
    importlib.invalidate_caches()


def _pieces(index, seed=0, **broken):
    """The toy cell's trainer objective, reference and parameters, as
    `harness._set_up` builds them; `broken` overrides model fields."""
    cell = harness.load_cell(index, "toy-routed-1c")
    model = harness.make_model(cell).clone(**broken)
    trainer = importlib.import_module(
        "benchmark.trainers.toy_two_terms").build(
            model, cell.phases[0], jax.devices()[:1], seed)
    ref = importlib.import_module("benchmark.reference.toy_routed_ref")
    return cell, trainer, ref, trainer.params(trainer.init())


def _errors(cell, trainer, ref, params, seed=1):
    return correct.measure_against_reference(
        trainer.objective, ref, params, cell.dims, SEQ, seed,
        cell.config["grad_leaves"])


# ------------------------------------------------------------- the cases

def test_a_routed_toy_added_by_files_alone_runs_to_correct(copy, monkeypatch,
                                                           capsys):
    result = _run(copy, "toy-routed-1c", True, monkeypatch, seconds=2.0)
    info = [json.loads(line[len("info: "):])
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("info: {")]
    checks = next(i for i in info if "checks" in i)
    assert {k for k, ok in checks["checks"].items() if not ok} == {
        "platform_is_tpu"}, checks
    # Compared on the configuration's own leaves, and on its choices.
    errors = next(i for i in info if "reference_errors" in i)
    assert set(errors["reference_errors"]) == {
        "logits", "choice_slack", "grad_norm", "grad.router",
        "grad.experts.wi"}
    assert result["compared"]["choice_slack"]["limit"] == correct.CHOICE_TOL
    assert result["compared"]["choice_slack"]["value"] <= correct.CHOICE_TOL
    # The kernel's roofline share from the configuration's head sizes. By
    # hand, one causal forward call at batch 2, seq 256, 2 heads of 24 /
    # 16: Q, K 2 x 49,152 B + V, O 2 x 32,768 B + 4,096 B of logsumexp =
    # 167,936 B, 0.205 us at 819 GB/s (its 5.2 MFLOP take 0.027 us); the
    # made-up trace holds 1 ms of the kernel a step, 3 calls a step.
    share = result["metrics"]["flash_attention_fwd_roofline"]["value"]
    assert share == pytest.approx(100 * 3 * 167936 / 819e9 / 1e-3, rel=1e-9)
    # The regions of the made-up chip trace, through `ctx.regions`.
    assert result["metrics"]["forward_ms_per_step"]["value"] == \
        pytest.approx(4.0)
    assert result["metrics"]["step_call_host_ms"]["value"] == \
        pytest.approx(1.0)
    assert "wrap_step_prepare_host_ms" not in result["metrics"]


def test_choices_differ_and_only_the_new_comparison_passes(copy):
    """bf16 and float32 disagree on some token's experts (a slack above
    0). A reference that chooses for itself (the comparison before this
    contract) is then far off in the logits however right the program
    is; given the program's choices it agrees, and the slack says the
    choices were ones rounding explains."""
    cell, trainer, ref, params = _pieces(copy)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cell.dims["vocab_size"], size=(1, SEQ), dtype=np.int32))
    _, logits, _ = jax.jit(trainer.objective, static_argnums=2)(
        params, ids, SEQ)
    alone, _ = ref.forward(params, ids, cell.dims, {})
    assert float(correct._rel_max(logits, alone)) > 2 * correct.LOGITS_TOL

    errors = _errors(cell, trainer, ref, params)
    assert correct.beyond_tolerance(errors) == {}
    assert 0 < errors["choice_slack"] < correct.CHOICE_TOL / 2


@pytest.mark.parametrize("pick", ["worst", "fewer", "next"])
def test_wrong_choices_fail_on_the_slack_alone(copy, pick):
    """The k worst experts, k - 1 of them, or rank k + 1 in place of
    rank k: the reference follows the choices, so logits and gradients
    still agree; the slack does not."""
    errors = _errors(*_pieces(copy, pick=pick))
    assert set(correct.beyond_tolerance(errors)) == {"choice_slack"}
    if pick == "worst":
        assert errors["choice_slack"] > 10 * correct.CHOICE_TOL
    elif pick == "next":
        assert errors["choice_slack"] > 2 * correct.CHOICE_TOL
    else:
        assert np.isnan(errors["choice_slack"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_choice_tol_separates_rounding_from_the_nearest_wrong_selection(seed):
    """The readings CHOICE_TOL was set between (`correct.py`), at the
    size of a routed layer: 4096 tokens of width 2048, a 2048 x 256
    router of std 0.02, sigmoid scores, top 8. Sound: the input
    perturbed by 0.6% and rounded to bf16, and the router in bf16
    throughout (input, weights, product, sigmoid), change hundreds of
    tokens' expert sets and read under 0.04. Wrong: rank 9 in place of
    rank 8 at every token reads over 0.2; the 8 worst read 4.4."""
    tokens, width, experts, k = 4096, 2048, 256, 8
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((tokens, width)).astype(np.float32)
    w = (0.02 * rng.standard_normal((width, experts))).astype(np.float32)

    def scores_of(x, w):
        with jax.default_matmul_precision("highest"):
            return jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(w))

    scores = scores_of(x, w)
    own = jax.lax.top_k(scores, k + 1)[1]

    def read(chosen):
        flipped = jnp.any(jnp.sort(chosen, -1) != jnp.sort(own[:, :k], -1),
                          axis=-1)
        return int(jnp.sum(flipped)), float(correct.choice_slack(
            scores, chosen.astype(jnp.int32), k))

    noisy = (x * (1 + 0.006 * rng.standard_normal(x.shape))).astype(
        jnp.bfloat16).astype(np.float32)
    flipped, slack = read(jax.lax.top_k(scores_of(noisy, w), k)[1])
    assert flipped > 100 and 0 < slack < 0.4 * correct.CHOICE_TOL
    half = jax.nn.sigmoid(jnp.asarray(x, jnp.bfloat16)
                          @ jnp.asarray(w, jnp.bfloat16))
    assert half.dtype == jnp.bfloat16
    flipped, slack = read(jax.lax.top_k(half, k)[1])
    assert flipped > 500 and 0 < slack < 0.4 * correct.CHOICE_TOL

    assert read(jnp.delete(own, k - 1, axis=-1))[1] > 2 * correct.CHOICE_TOL
    assert read(jax.lax.top_k(-scores, k)[1])[1] > 40 * correct.CHOICE_TOL


def test_a_reference_without_a_slack_fails_where_choices_were_sown(copy):
    cell, trainer, ref, params = _pieces(copy)
    mute = types.SimpleNamespace(
        loss=ref.loss,
        forward=lambda *a, **kw: (ref.forward(*a, **kw)[0], None))
    errors = _errors(cell, trainer, mute, params)
    assert set(correct.beyond_tolerance(errors)) == {"choice_slack"}


def test_a_reference_that_omits_the_second_term_fails(copy):
    """The trainer's objective is what is compared: a reference loss
    without the step's second term is far off in the gradients (the
    harness once rebuilt `lm_loss` alone on both sides, and such a step
    was timed with the term and compared without it)."""
    cell, trainer, ref, params = _pieces(copy)
    short = types.SimpleNamespace(
        forward=ref.forward,
        loss=functools.partial(ref.loss, second_weight=0.0))
    errors = _errors(cell, trainer, short, params)
    failed = correct.beyond_tolerance(errors)
    assert "grad_norm" in failed and "logits" not in failed
    assert errors["grad_norm"] > 5 * correct.GRAD_TOL


def test_other_grad_leaves_are_compared(copy):
    cell, trainer, ref, params = _pieces(copy)
    errors = correct.measure_against_reference(
        trainer.objective, ref, params, cell.dims, SEQ, 1,
        {"head": ["head", "kernel"]})
    assert {k for k in errors if k.startswith("grad.")} == {"grad.head"}
    assert correct.beyond_tolerance(errors) == {}


def test_choice_slack_by_hand():
    """Five items, k = 2, scores of deviation s: the reference's second
    best is 0.7. Choosing (0.9, 0.7) reads 0; (0.9, 0.6) reads 0.1 / s;
    one item, or the same item twice, reads NaN."""
    scores = jnp.asarray([[0.9, 0.7, 0.6, 0.2, 0.1]], jnp.float32)
    s = float(jnp.std(scores))

    def slack(chosen):
        return float(correct.choice_slack(
            scores, jnp.asarray([chosen], jnp.int32), 2))

    assert slack([1, 0]) == 0.0
    assert slack([0, 2]) == pytest.approx(0.1 / s, rel=1e-5)
    assert slack([4, 3]) == pytest.approx(0.6 / s, rel=1e-5)
    assert np.isnan(slack([0])) and np.isnan(slack([0, 0]))


@pytest.mark.parametrize("backward", [False, True])
def test_attention_cost_with_unequal_head_sizes_equals_the_hand_count(
        backward):
    """32 heads with queries and keys of 192 and values of 128, batch 2,
    4096 tokens, causal. Forward a head: scores 2 S^2 192 + weighted sum
    2 S^2 128 = 640 S^2; backward twice that; halved by the mask. Bytes:
    Q, K (and dQ, dK) at 192, V, O (and dO, dV) at 128, two bytes each,
    plus the f32 logsumexp."""
    b, s, h = 2, 4096, 32
    got_flops, got_bytes = flops.attention_kernel_cost(
        batch=b, seq=s, heads=h, qk_head_dim=192, v_head_dim=128,
        causal=True, backward=backward)
    passes = 2 if backward else 1
    assert got_flops == passes * b * h * 640 * s * s / 2
    rows = b * s * h
    assert got_bytes == passes * (2 * rows * 192 * 2 + 2 * rows * 128 * 2) \
        + rows * 4
    # Equal sizes: the count the GPT-2 cells have always had.
    assert flops.attention_kernel_cost(4, s, 12, 64, 64, True, backward) == (
        passes * 2 * 2.0 * 4 * 12 * s * s * 64 / 2,
        passes * 4.0 * 4 * s * 12 * 64 * 2 + 4 * 12 * s * 4)
