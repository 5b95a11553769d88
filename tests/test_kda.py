"""The chunked gated delta rule (ops/kda.py) against the rule taken one
position at a time (`benchmark/reference/linear_moe_ref.py::delta_rule`),
kernels interpreted on the CPU: outputs and the gradients of every input
(q, k, v, g, beta, and the output norm's gate and scale), over several
chunk counts, a sequence that is no whole number of chunks, and decays
at both ends of `A_log`'s initial range and past it, where an unanchored
chunk form overflows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.linear_moe_ref import _l2, _norm, delta_rule
from horovod_tpu.models import linear_moe
from horovod_tpu.ops import kda

B, H, D = 2, 2, 16
EPS = 1e-5


def _inputs(seq, decay, seed=0):
    """q and k of any length (keys all positive, as SiLU's mostly are, so
    that they are alike once normed), values, g = -exp(A_log)
    softplus(dt) with `decay` the largest exp(A_log) and dt at its
    initial range's top, beta, and the output norm's gate and scale."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, seq, H, D))
    k = np.abs(rng.normal(size=(B, seq, H, D))) + 0.3
    v = rng.normal(size=(B, seq, H, D))
    softplus = np.log1p(np.exp(rng.uniform(-7, np.log(
        linear_moe.DT_RANGE[1]), size=(B, seq, H, D))))
    g = -rng.uniform(linear_moe.A_RANGE[0], decay,
                     size=(1, 1, H, 1)) * softplus
    beta = rng.uniform(0.05, 0.95, size=(B, seq, H))
    gate = rng.normal(size=(B, seq, H, D))
    scale = rng.uniform(0.5, 1.5, size=(D,))
    return [jnp.asarray(x, jnp.float32)
            for x in (q, k, v, g, beta, gate, scale)]


def _recurrence(q, k, v, g, beta, gate=None, scale=None):
    with jax.default_matmul_precision("highest"):
        o = delta_rule(_l2(q), _l2(k), v, g, beta)
        if gate is None:
            return o
        return _norm(o, scale, EPS) * jax.nn.sigmoid(gate)


def _chunked(q, k, v, g, beta, gate=None, scale=None, chunk=16):
    flat = lambda x: x.reshape(*x.shape[:2], -1)
    out = kda.kda(flat(q), flat(k), flat(v), flat(g), beta,
                  gate=None if gate is None else flat(gate), scale=scale,
                  eps=EPS, chunk=chunk)
    return out.reshape(v.shape)


def _compare(args, chunk, tol=1e-4):
    """Values and every gradient within `tol` of the largest entry."""
    want = _recurrence(*args)
    got = _chunked(*args, chunk=chunk)
    assert got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale
    cotangent = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                            jnp.float32)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * cotangent),
                      argnums=range(len(args)))(*args)
             for f in (_recurrence,
                       lambda *a: _chunked(*a, chunk=chunk))]
    names = "q k v g beta gate scale".split()
    for name, w, g in zip(names, *grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        err = float(jnp.max(jnp.abs(g - w))) / float(jnp.max(jnp.abs(w)))
        assert err <= tol, (name, err)


@pytest.mark.parametrize("seq,chunk", [(16, 16), (48, 16), (128, 32),
                                       (200, 64), (37, 8)])
@pytest.mark.parametrize("decay", [linear_moe.A_RANGE[0],
                                   linear_moe.A_RANGE[1], 400.0])
def test_chunks_equal_the_recurrence(seq, chunk, decay):
    """The plain output; a decay of 400 (Gamma below -2000 within a
    chunk) stays finite."""
    _compare(_inputs(seq, decay)[:5], chunk)


@pytest.mark.parametrize("seq,chunk", [(48, 16), (200, 64)])
def test_the_gated_output_norm_equals_the_recurrence_normed(seq, chunk):
    """RMSNorm over each head's channels, the scale, sigmoid(gate), formed
    in the kernels: values and the gradients of all seven inputs."""
    _compare(_inputs(seq, linear_moe.A_RANGE[1]), chunk)


def test_bf16_inputs_keep_their_dtype_in_the_gradients():
    """q, k, v and the gate in bfloat16 as the model hands them: the
    output is in v's dtype, each gradient comes back in its input's
    dtype."""
    q, k, v, g, beta, gate, scale = _inputs(32, 4.0)
    flat = lambda x: x.reshape(B, 32, -1).astype(jnp.bfloat16)
    q, k, v, gate = map(flat, (q, k, v, gate))
    g = g.reshape(B, 32, -1)
    out, vjp = jax.vjp(lambda *a: kda.kda(*a[:5], gate=a[5], scale=a[6],
                                          chunk=16),
                       q, k, v, g, beta, gate, scale)
    assert out.dtype == jnp.bfloat16
    grads = vjp(jnp.ones_like(out))
    assert [x.dtype for x in grads] == (
        [jnp.bfloat16] * 3 + [jnp.float32] * 2 + [jnp.bfloat16, jnp.float32])


def test_a_position_past_the_end_writes_nothing():
    """Padding to whole chunks adds positions with q = k = v = g = beta =
    0: the outputs of the real positions are those of the longer
    sequence's prefix."""
    args = _inputs(40, 8.0)[:5]
    short = _chunked(*args)
    longer = _chunked(*(jnp.concatenate([x, x[:, :8]], axis=1)
                        for x in args))
    np.testing.assert_allclose(np.asarray(short), np.asarray(longer[:, :40]),
                               rtol=0, atol=1e-5)


def test_the_state_is_what_the_gauges_count():
    assert kda.chunks_of(8192) == 128
    assert kda.chunks_of(8193) == 129
    assert kda.state_bytes(32, 128, 128) == 32 * 128 * 128 * 4
