"""Kimi Delta Attention (KDA): the gated delta rule with a decay for each
key channel, over whole sequences, in the chunked (WY) form, forward
and backward in Pallas kernels.

Per head, with keys and queries of Dk, values of Dv, a state S (Dk, Dv)
from S_0 = 0, and for every position t a log-decay g_t (Dk,) <= 0 and a
write strength beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(Dk)

**The chunked form.** Positions are taken C = `chunk` at a time. Within
a chunk, with Gamma the running sum of g over the chunk, S_0 the state
the chunk starts from, K^ = K e^Gamma, Q^ = Q e^Gamma / sqrt(Dk):

    A_ij = beta_i <k_i e^Gamma_i, k_j e^-Gamma_j>        i > j (else 0)
    T = (I + A)^-1,  U = T diag(beta) V,  W = T diag(beta) K^
    P_ij = <q_i e^Gamma_i, k_j e^-Gamma_j> / sqrt(Dk)   i >= j (else 0)
    U~ = U - W S_0
    O = Q^ S_0 + P U~
    S_C = e^Gamma_C * S_0 + (K e^(Gamma_C - Gamma))^T U~

**The kernels.** One program takes Hb heads of one (batch row, chunk),
Hb = `heads_per_program(...)`: the heads are independent, state and all,
so each quantity below is a (Hb, ...) stack and each product a batched
one, a head's arithmetic the same op for op as alone, and the scheduler
has Hb chains to fill the slots one head's dependent chain leaves empty.
The grid runs the chunks innermost and in order, and the states, held
transposed (Dv, Dk) so that the decay scales their columns, stay in VMEM
scratch from one chunk to the next (`_forward`). The forward kernel
also writes the state each chunk starts from, in float32 (0.54 GB a
layer at 8192 positions, 2 rows and 32 heads of 128: the backward pass
forms U~ = U - W S_0 again from it, a difference that a rounded state
would blur). The backward kernel runs
the chunks in reverse with dS in scratch: it forms the chunk's
quantities again from q, k, v, g, beta and the stored state and
differentiates them by hand (`_backward`). q, k, v, g and o are read and
written in the projections' own (B, S, H * D) layout, a head being a
column block: a 4-D view of that layout is a copy, so what works on a
head's row is done in the kernels, on the block: the L2 norms of q and
k, and where asked the output's per-head RMSNorm and sigmoid gate.

**No exponent is positive.** The factors e^Gamma_i and e^-Gamma_j of a
score overflow within a chunk at strong decays (Gamma reaches -100 in 64
positions at the decays `A_log`'s initial range allows), so scores are
taken by 16-wide sub-chunks. Rows of sub-chunk a against keys before it
are products of x_i e^(Gamma_i - r) and k_j e^(r - Gamma_j), with the
anchor r = Gamma at a's first position: both exponents are <= 0. The
blocks on the diagonal are formed elementwise, a diagonal i - j = delta
at a time: sum_d x_id k_(i-delta)d e^(Gamma_id - Gamma_(i-delta)d).
Every other factor, e^Gamma, e^(Gamma_C - Gamma), e^Gamma_C, is <= 1
already.

**(I + A)^-1 without a loop.** Each 16 x 16 diagonal block of A is
nilpotent, so I + A_d (A_d: the diagonal blocks) has the exact inverse
(I - A_d)(I + A_d^2)(I + A_d^4)(I + A_d^8); with N = (I + A_d)^-1 A_l (A_l:
the blocks below) nilpotent over the four block rows,
T = (I - N)(I + N^2)(I + A_d)^-1. The same product over a whole chunk of
64 would be exact too, but its factors grow as binomial coefficients
where keys are alike (SiLU's keys are) and cancel in float32.

Everything inside the kernels is float32 at the highest matmul
precision: the state carries a whole sequence of sums.
"""
from __future__ import annotations

import functools
import math
import types
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_platform import call_by_platform

# Positions a chunk, and the width of the sub-chunks whose scores are
# anchored; the L2 norm of q and k: x * rsqrt(sum(x^2) + L2_EPS).
CHUNK = 64
SUB = 16
L2_EPS = 1e-6

# The most heads one program takes: the largest count timed on a v5e
# that paid (PERF.md); the VMEM the kernels ask for (the v5e's default
# is 16 MiB, which the backward outgrows at 4 heads).
HEADS_PER_PROGRAM = 4
VMEM_LIMIT = 32 * 2 ** 20

# Contractions of two (heads, m, n) stacks, one product a head.
_NN = ((2,), (1,))      # a b
_TN = ((1,), (1,))      # a^T b
_NT = ((2,), (2,))      # a b^T


def chunks_of(seq: int, chunk: int = CHUNK) -> int:
    """Chunks a call over `seq` positions makes (the sequence is padded
    to whole chunks)."""
    return -(-seq // chunk)


def state_bytes(heads: int, key_dim: int, value_dim: int) -> int:
    """Bytes of the float32 states of one sequence of a call: one a head,
    carried from chunk to chunk, and one a head and chunk kept for the
    backward pass."""
    return heads * key_dim * value_dim * 4


def heads_per_program(heads: int, key_dim: int, value_dim: int) -> int:
    """Heads one program of the kernels takes: the most, up to
    HEADS_PER_PROGRAM, that divide `heads` and whose blocks, states and
    temporaries fit VMEM_LIMIT (`_vmem_bytes`)."""
    for n in range(min(heads, HEADS_PER_PROGRAM), 1, -1):
        if heads % n == 0 and _vmem_bytes(n, key_dim, value_dim) \
                <= VMEM_LIMIT:
            return n
    return 1


def _vmem_bytes(heads: int, key_dim: int, value_dim: int) -> int:
    """VMEM the backward kernel, the larger, holds at `heads` a program:
    168 float32 (CHUNK, D) arrays a head. Mosaic's compile for a v5e at
    chunks of 64 and heads of 128 took 5.25, 8.72, 19.88 and 41.25 MiB
    at 1, 2, 4 and 8 heads (the forward 1.78-16.69)."""
    return heads * 168 * CHUNK * max(key_dim, value_dim) * 4


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(n):
    return (_iota((n, n), 0) == _iota((n, n), 1)).astype(jnp.float32)


def _neumann(a, order: int):
    """(I + a)^-1 of a stack of matrices with a^order = 0, order a power
    of two: (I - a)(I + a^2)(I + a^4)... (I + a^(order / 2))."""
    t, power = _eye(a.shape[-1]) - a, a
    for _ in range(int(math.log2(order)) - 1):
        power = _dot(power, power)
        t = t + _dot(t, power)
    return t


# --------------------------------------------------- one chunk of Hb heads

class _Chunk:
    """The chunk's quantities, a stack of Hb heads each, from float32 q,
    k, g (Hb, C, Dk), v (Hb, C, Dv), beta (Hb, C, 1) (module docstring;
    `qs` is q / sqrt(Dk)). Masks are (C, C) or (C, 1), broadcast over
    the heads."""

    def __init__(self, q, k, v, g, beta, sub: int):
        n, C, Dk = k.shape
        self.sub, self.k, self.v, self.beta = sub, k, v, beta
        self.qs = q * (1.0 / math.sqrt(Dk))
        self.rows = _iota((C, 1), 0)
        i, j = _iota((C, C), 0), _iota((C, C), 1)
        self.lower = (i >= j).astype(jnp.float32)
        self.strict = (i > j).astype(jnp.float32)
        self.same = (i // sub == j // sub)
        self.stacked_lower = jnp.broadcast_to(self.lower, (n, C, C))
        self.G = _dot(self.stacked_lower, g)                  # Gamma
        self.last = self.G[:, C - 1:C, :]                     # (Hb, 1, Dk)
        self.E = jnp.exp(self.G)
        self.qh, self.kh = self.qs * self.E, k * self.E
        self.tail = jnp.exp(self.last - self.G)
        self.kb = k * self.tail
        self.gamma = jnp.exp(self.last)
        P, A = self.scores()
        self.P, self.A0 = P, A                                # A0 before beta
        A = beta * A
        within = jnp.where(self.same, A, 0.0)
        self.T = _neumann(within, sub)                        # (I + A_d)^-1
        if C > sub:
            N = _dot(self.T, A - within)
            self.T = _dot(_neumann(N, C // sub), self.T)
        self.U = _dot(self.T, beta * v)
        self.W = _dot(self.T, beta * self.kh)

    def anchored(self, a: int):
        """(down, up): e^(Gamma_i - r) on sub-chunk a's rows and
        e^(r - Gamma_j) on the rows before it, zeros elsewhere."""
        r = self.G[:, a * self.sub:a * self.sub + 1, :]
        block = self.rows // self.sub
        down = jnp.where(block == a,
                         jnp.exp(jnp.minimum(self.G - r, 0.0)), 0.0)
        up = jnp.where(self.rows < a * self.sub,
                       jnp.exp(jnp.minimum(r - self.G, 0.0)), 0.0)
        return down, up

    def diagonal(self, delta: int):
        """(e, on): e^(Gamma_t - Gamma_(t - delta)) on rows t of a
        sub-chunk that have a row delta above them in it (else 0), and
        the (C, C) mask of the diagonal i - j = delta within
        sub-chunks."""
        C = self.G.shape[1]
        shifted = pltpu.roll(self.G, delta, 1) if delta else self.G
        e = jnp.where(self.rows % self.sub >= delta,
                      jnp.exp(jnp.minimum(self.G - shifted, 0.0)), 0.0)
        i, j = _iota((C, C), 0), _iota((C, C), 1)
        on = ((i - j == delta) & self.same).astype(jnp.float32)
        return e, on

    def shifted_k(self, delta: int):
        return pltpu.roll(self.k, delta, 1) if delta else self.k

    def scores(self):
        """P (i >= j) and A before beta (i > j), (Hb, C, C) each."""
        n, C, _ = self.k.shape
        P = A = jnp.zeros((n, C, C), jnp.float32)
        for a in range(1, C // self.sub):
            down, up = self.anchored(a)
            keys = self.k * up
            P = P + _dot(self.qs * down, keys, _NT)
            A = A + _dot(self.k * down, keys, _NT)
        for delta in range(self.sub):
            e, on = self.diagonal(delta)
            kd = self.shifted_k(delta) * e
            P = P + jnp.sum(self.qs * kd, axis=-1, keepdims=True) * on
            if delta:
                A = A + jnp.sum(self.k * kd, axis=-1, keepdims=True) * on
        return P, A

    def step(self, st):
        """(O, the states after the chunk, U~) from the transposed states
        st (Hb, Dv, Dk) the chunk starts from."""
        u = self.U - _dot(self.W, st, _NT)
        o = _dot(self.qh, st, _NT) + _dot(self.P, u)
        return o, st * self.gamma + _dot(u, self.kb, _TN), u

    def backward(self, st, u, d_o, d_after):
        """(dq, dk, dv, dg, dbeta, d_st): the gradients of the chunk's
        inputs and of the states it starts from, given those of its
        output and of the states after it (both transposed)."""
        C = self.k.shape[1]
        # The state's update and the output.
        d_gamma = jnp.sum(st * d_after, axis=1, keepdims=True)
        d_kb = _dot(u, d_after)
        d_u = _dot(self.kb, d_after, _NT) + _dot(self.P, d_o, _TN)
        d_qh = _dot(d_o, st)
        d_P = _dot(d_o, u, _NT) * self.lower
        d_st = (d_after * self.gamma + _dot(d_o, self.qh, _TN)
                - _dot(d_u, self.W, _TN))
        # U~ = U - W S_0, then X = T R with R = beta [V | K^].
        d_W = -_dot(d_u, st)
        d_rv = _dot(self.T, d_u, _TN)
        d_rk = _dot(self.T, d_W, _TN)
        d_A = -(_dot(d_rv, self.U, _NT)
                + _dot(d_rk, self.W, _NT)) * self.strict
        d_v = self.beta * d_rv
        d_kh = self.beta * d_rk
        d_beta = (jnp.sum(d_rv * self.v + d_rk * self.kh, axis=-1,
                          keepdims=True)
                  + jnp.sum(d_A * self.A0, axis=-1, keepdims=True))
        d_A = self.beta * d_A
        # The scores; Gamma enters a score as + Gamma_i - Gamma_j, so its
        # gradient is x * dx - k * dk_column (row side x = qs or k).
        d_qs = d_k_row = d_k_col = jnp.zeros_like(self.k)
        for a in range(1, C // self.sub):
            down, up = self.anchored(a)
            keys = self.k * up
            d_qs = d_qs + _dot(d_P, keys) * down
            d_k_row = d_k_row + _dot(d_A, keys) * down
            d_k_col = d_k_col + (_dot(d_P, self.qs * down, _TN)
                                 + _dot(d_A, self.k * down, _TN)) * up
        for delta in range(self.sub):
            e, on = self.diagonal(delta)
            kd = self.shifted_k(delta) * e
            dp = jnp.sum(d_P * on, axis=-1, keepdims=True)
            da = jnp.sum(d_A * on, axis=-1, keepdims=True)
            d_qs = d_qs + dp * kd
            d_k_row = d_k_row + da * kd
            back = (dp * self.qs + da * self.k) * e
            d_k_col = d_k_col + (pltpu.roll(back, C - delta, 1) if delta
                                 else back)
        d_G = self.qs * d_qs + self.k * (d_k_row - d_k_col)
        d_k = d_k_row + d_k_col
        # Q^ = qs e^Gamma, K^ = k e^Gamma, K~ = k e^(Gamma_C - Gamma),
        # gamma = e^Gamma_C; then Gamma = L g.
        d_qs = d_qs + d_qh * self.E
        d_k = d_k + d_kh * self.E + d_kb * self.tail
        d_G = d_G + d_qh * self.qh + d_kh * self.kh - d_kb * self.kb
        d_last = (jnp.sum(d_kb * self.kb, axis=1, keepdims=True)
                  + d_gamma * self.gamma)
        d_G = d_G + jnp.where(self.rows == C - 1, d_last, 0.0)
        d_g = _dot(self.stacked_lower, d_G, _TN)
        d_q = d_qs * (1.0 / math.sqrt(self.k.shape[2]))
        return d_q, d_k, d_v, d_g, d_beta, d_st


# ------------------------------------------------------- around the chunk

def _unit(x):
    """(x / |x|, 1 / |x|) of each row, |x| = sqrt(sum x^2 + L2_EPS)."""
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return x * r, r


def _unit_bwd(unit, r, d_unit):
    """The gradient of a row before `_unit` from that of the unit row."""
    return r * (d_unit - unit * jnp.sum(d_unit * unit, axis=-1,
                                        keepdims=True))


def _gated_norm(o, gate, scale, eps):
    """(RMSNorm(o) * scale * sigmoid(gate), the normed rows, the
    reciprocal RMS, sigmoid(gate)): the norm over each row of a head."""
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    normed, sig = o * r, jax.nn.sigmoid(gate)
    return normed * scale * sig, normed, r, sig


def _gated_norm_bwd(normed, r, sig, scale, d_y):
    """(dO, dgate, dscale summed over each head's rows) from dY."""
    d_normed = d_y * scale * sig
    d_o = r * (d_normed - normed * jnp.mean(d_normed * normed, axis=-1,
                                            keepdims=True))
    d_gate = d_y * scale * normed * sig * (1.0 - sig)
    return d_o, d_gate, jnp.sum(d_y * normed * sig, axis=1, keepdims=True)


# -------------------------------------------------------------- the kernels

def _heads(ref, heads: int):
    """The (heads, C, D) float32 stack of a (1, C, heads * D) column
    block's heads."""
    D = ref.shape[2] // heads
    return jnp.stack([ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
                      for h in range(heads)])


def _store(ref, x):
    """Writes the (Hb, C, D) stack x to its (1, C, Hb * D) column block."""
    D = x.shape[2]
    for h in range(x.shape[0]):
        ref[0, :, h * D:(h + 1) * D] = x[h].astype(ref.dtype)


def _load(refs, sub):
    """The chunk of q, k (L2-normed here), v, g, beta from their refs:
    (chunk, q / |q|, 1 / |q|, k / |k|, 1 / |k|)."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    heads = beta_ref.shape[1]
    q, rq = _unit(_heads(q_ref, heads))
    k, rk = _unit(_heads(k_ref, heads))
    return _Chunk(q, k, _heads(v_ref, heads), _heads(g_ref, heads),
                  beta_ref[0], sub), q, rq, k, rk


def _forward_kernel(*refs, sub, gated, eps):
    inputs, refs = refs[:5], refs[5:]
    if gated:
        (gate_ref, scale_ref), refs = refs[:2], refs[2:]
    o_ref, start_ref, st_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    chunk = _load(inputs, sub)[0]
    st = st_ref[...]
    start_ref[0, :, 0] = st
    o, st_ref[...], _ = chunk.step(st)
    if gated:
        o = _gated_norm(o, _heads(gate_ref, o.shape[0]), scale_ref[...],
                        eps)[0]
    _store(o_ref, o)


def _backward_kernel(*refs, sub, gated, eps):
    inputs, refs = refs[:5], refs[5:]
    if gated:
        (gate_ref, scale_ref), refs = refs[:2], refs[2:]
    start_ref, dy_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref = refs[:7]
    dgate_ref, dscale_ref, ds_ref = refs[7:] if gated else (None, None,
                                                             refs[7])

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    chunk, q, rq, k, rk = _load(inputs, sub)
    heads = q.shape[0]
    st = start_ref[0, :, 0]
    o, _, u = chunk.step(st)
    d_o = _heads(dy_ref, heads)
    if gated:
        scale = scale_ref[...]
        _, normed, r, sig = _gated_norm(o, _heads(gate_ref, heads), scale,
                                        eps)
        d_o, d_gate, dscale_ref[0, :, 0] = _gated_norm_bwd(
            normed, r, sig, scale, d_o)
        _store(dgate_ref, d_gate)
    d_q, d_k, d_v, d_g, d_beta, ds_ref[...] = chunk.backward(
        st, u, d_o, ds_ref[...])
    _store(dq_ref, _unit_bwd(q, rq, d_q))
    _store(dk_ref, _unit_bwd(k, rk, d_k))
    _store(dv_ref, d_v)
    _store(dg_ref, d_g)
    dbeta_ref[0] = d_beta


def _specs(C, Dk, Dv, N, heads, reverse: bool):
    """Blocks at grid point (b, h, n), the chunks backwards where
    `reverse`: of the h-th `heads` heads of one (batch row, chunk) of a
    (B, S, H * D) array at D = Dk and Dv, of beta (B, H, S, 1), of the
    states (B, H, N, Dv, Dk) and of the per-chunk sums (B, H, N, 1, Dv);
    the scale (1, Dv) whole."""
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)
    columns = lambda D: pl.BlockSpec((1, C, heads * D),
                                     lambda b, h, n: (b, at(n), h))
    return types.SimpleNamespace(
        keys=columns(Dk), values=columns(Dv),
        rows=pl.BlockSpec((1, heads, C, 1),
                          lambda b, h, n: (b, h, at(n), 0)),
        states=pl.BlockSpec((1, heads, 1, Dv, Dk),
                            lambda b, h, n: (b, h, at(n), 0, 0)),
        sums=pl.BlockSpec((1, heads, 1, 1, Dv),
                          lambda b, h, n: (b, h, at(n), 0, 0)),
        scale=pl.BlockSpec((1, Dv), lambda b, h, n: (0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "eps", "heads", "interpret"))
def _forward(q, k, v, g, beta, gate, scale, *, chunk, eps, heads=None,
             interpret=None):
    """(the output (B, S, H * Dv) and the states each chunk starts from
    (B, H, N, Dv, Dk)), the first in v's dtype, the states in float32.
    `gate` and `scale` None: o. `heads` a program, a divisor of H:
    `heads_per_program`'s where None."""
    B, H, S = beta.shape[:3]
    Dk, Dv, N = q.shape[-1] // H, v.shape[-1] // H, S // chunk
    heads = heads or heads_per_program(H, Dk, Dv)
    spec = _specs(chunk, Dk, Dv, N, heads, False)
    gated = gate is not None
    extra = [gate, scale.reshape(1, Dv)] if gated else []

    def call(interp: bool):
        return pl.pallas_call(
            functools.partial(_forward_kernel, sub=min(SUB, chunk),
                              gated=gated, eps=eps),
            grid=(B, H // heads, N),
            in_specs=[spec.keys, spec.keys, spec.values, spec.keys,
                      spec.rows] + ([spec.values, spec.scale] if gated
                                    else []),
            out_specs=[spec.values, spec.states],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct((B, H, N, Dv, Dk),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((heads, Dv, Dk), jnp.float32)],
            compiler_params=_params(), interpret=interp, name="kda_fwd")

    return call_by_platform(call, q, k, v, g, beta, *extra,
                            interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "eps", "heads", "interpret"))
def _backward(q, k, v, g, beta, gate, scale, starts, d_y, *, chunk, eps,
              heads=None, interpret=None):
    """The gradients of q, k, v, g, beta (and gate, scale), in their
    shapes and dtypes; `heads` as `_forward`'s."""
    B, H, S = beta.shape[:3]
    Dk, Dv, N = q.shape[-1] // H, v.shape[-1] // H, S // chunk
    heads = heads or heads_per_program(H, Dk, Dv)
    spec = _specs(chunk, Dk, Dv, N, heads, True)
    gated = gate is not None
    extra = [gate, scale.reshape(1, Dv)] if gated else []
    grads = [q, k, v, g, beta] + ([gate] if gated else [])

    def call(interp: bool):
        return pl.pallas_call(
            functools.partial(_backward_kernel, sub=min(SUB, chunk),
                              gated=gated, eps=eps),
            grid=(B, H // heads, N),
            in_specs=[spec.keys, spec.keys, spec.values, spec.keys,
                      spec.rows] + ([spec.values, spec.scale] if gated
                                    else []) + [spec.states, spec.values],
            out_specs=[spec.keys, spec.keys, spec.values, spec.keys,
                       spec.rows] + ([spec.values, spec.sums] if gated
                                     else []),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in grads] + (
                [jax.ShapeDtypeStruct((B, H, N, 1, Dv), jnp.float32)]
                if gated else []),
            scratch_shapes=[pltpu.VMEM((heads, Dv, Dk), jnp.float32)],
            compiler_params=_params(), interpret=interp, name="kda_bwd")

    out = call_by_platform(call, q, k, v, g, beta, *extra, starts, d_y,
                           interpret=interpret)
    if not gated:
        return (*out, None, None)
    return (*out[:6], jnp.sum(out[6], axis=(0, 1, 2, 3)).astype(scale.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _kda(q, k, v, g, beta, gate, scale, chunk, eps, interpret):
    return _forward(q, k, v, g, beta, gate, scale, chunk=chunk, eps=eps,
                    interpret=interpret)[0]


def _kda_fwd(q, k, v, g, beta, gate, scale, chunk, eps, interpret):
    out, starts = _forward(q, k, v, g, beta, gate, scale, chunk=chunk,
                           eps=eps, interpret=interpret)
    return out, (q, k, v, g, beta, gate, scale, starts)


def _kda_bwd(chunk, eps, interpret, residuals, d_y):
    return _backward(*residuals, d_y, chunk=chunk, eps=eps,
                     interpret=interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


# ------------------------------------------------------------------ the op

def kda(q, k, v, g, beta, *, gate=None, scale=None, eps: float = 1e-5,
        chunk: int = CHUNK, interpret: Optional[bool] = None):
    """The gated delta rule (module docstring) over whole sequences, heads
    as column blocks of the projections' layout.

    q, k (B, S, H * Dk), L2-normalised per head here
    (x * rsqrt(sum x^2 + L2_EPS)); v (B, S, H * Dv); g (B, S, H * Dk)
    log-decays <= 0, float32; beta (B, S, H) in (0, 1): H is read off
    it. Returns o (B, S, H * Dv) in v's dtype; given `gate` (B, S,
    H * Dv) and `scale` (Dv,), RMSNorm(o) * scale * sigmoid(gate)
    instead, the norm over each head's Dv channels with `eps`, formed in
    the kernels from the float32 o. The sequence is padded to whole
    chunks with positions that write nothing. `chunk` is 16 times a
    power of two, or a power of two up to 16; `interpret` forces the
    kernels' mode (ops/pallas_platform.py)."""
    S = q.shape[1]
    pad = chunks_of(S, chunk) * chunk - S
    fill = lambda x: None if x is None else jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    rows = fill(beta.astype(jnp.float32)).transpose(0, 2, 1)[..., None]
    out = _kda(fill(q), fill(k), fill(v), fill(g.astype(jnp.float32)), rows,
               fill(gate), scale, chunk, eps, interpret)
    return out[:, :S]
