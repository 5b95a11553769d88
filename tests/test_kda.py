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


def _pinned(args, heads, chunk=16):
    """The kernels' output, chunk-start states and every gradient at
    `heads` a program (None: `heads_per_program`'s), the sequence padded
    to whole chunks as `kda.kda` pads it."""
    q, k, v, g, beta, gate, scale = args
    S = q.shape[1]
    pad = kda.chunks_of(S, chunk) * chunk - S
    fill = lambda x: None if x is None else jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    q, k, v, g, gate = map(fill, (q, k, v, g, gate))
    rows = fill(beta).transpose(0, 2, 1)[..., None]
    out, starts = kda._forward(q, k, v, g, rows, gate, scale, chunk=chunk,
                               eps=EPS, heads=heads)
    d_y = jnp.asarray(np.random.default_rng(1).normal(size=out.shape),
                      jnp.float32)
    grads = kda._backward(q, k, v, g, rows, gate, scale, starts, d_y,
                          chunk=chunk, eps=EPS, heads=heads)
    return [out, starts] + [x for x in grads if x is not None]


@pytest.mark.parametrize("seq", [24, 32])
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("heads", [1, 2, 3, 4, 6, 8])
def test_several_heads_a_program_equal_one(heads, gated, seq):
    """A program that takes Hb heads computes each head as a program of
    one does: outputs, chunk-start states and gradients at every divisor
    Hb of the heads, and at `heads_per_program`'s choice, against Hb = 1.
    On the chip they are equal to the bit; interpreted on the CPU, XLA's
    batched and unbatched products may round a last bit apart, so the
    limit here is 1e-6 of each array's largest entry."""
    rng = np.random.default_rng(heads)
    shape = (1, seq, heads * D)
    q, v, gate = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                  for _ in range(3))
    k = jnp.asarray(np.abs(rng.normal(size=shape)) + 0.3, jnp.float32)
    g = jnp.asarray(-rng.uniform(0.0, 2.0, size=shape), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, size=(1, seq, heads)),
                       jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, size=(D,)), jnp.float32)
    args = (q, k, v, g, beta) + ((gate, scale) if gated else (None, None))
    want = _pinned(args, 1)
    chosen = kda.heads_per_program(heads, D, D)
    for n in [n for n in range(2, heads + 1)
              if heads % n == 0 and n != chosen] + [None]:
        got = _pinned(args, n)
        assert len(got) == len(want) == (9 if gated else 7)
        for i, (x, y) in enumerate(zip(got, want)):
            assert x.shape == y.shape and x.dtype == y.dtype, (n, i)
            limit = 1e-6 * float(jnp.max(jnp.abs(y)))
            assert float(jnp.max(jnp.abs(x - y))) <= limit, (n, i)


def test_heads_per_program_divides_the_heads():
    """Kimi's 32 heads of 128 take the timed choice; a count it does not
    divide falls to the largest divisor below it; one head takes one;
    heads whose stacks overflow the kernels' VMEM take fewer."""
    assert kda.heads_per_program(32, 128, 128) == kda.HEADS_PER_PROGRAM
    assert kda.heads_per_program(6, 128, 128) == 3
    assert kda.heads_per_program(5, 128, 128) == 1
    assert kda.heads_per_program(2, 128, 128) == 2
    assert kda.heads_per_program(1, 128, 128) == 1
    assert kda.heads_per_program(32, 2048, 2048) == 1
    for heads in range(1, 65):
        n = kda.heads_per_program(heads, 128, 128)
        assert heads % n == 0 and 1 <= n <= kda.HEADS_PER_PROGRAM
        assert kda._vmem_bytes(n, 128, 128) <= kda.VMEM_LIMIT
