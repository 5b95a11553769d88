"""Fused BatchNorm-apply + ReLU + 1x1-conv + output-stats Pallas kernel.

Why: the ResNet-50 train step on TPU is HBM-bandwidth bound in the
BatchNorm passes, not MXU bound (see docs/benchmarks.md). XLA keeps
BN-apply and batch-stat reductions as separate passes over the
activations because it cannot fuse elementwise prologues/reduction
epilogues INTO a convolution. A 1x1 convolution is a plain matmul over
the channel dim, so Pallas can: this kernel reads the RAW (pre-BN)
input once, normalizes + ReLUs it in VMEM, feeds the MXU, and
accumulates the output's batch statistics (sum, sum-of-squares) in the
same pass — eliminating the normalize write+read and the stats read
that XLA pays around every 1x1 conv.

The reference has no analogue (its cuDNN convs are monolithic); this is
the "fuse elementwise into matmuls" TPU playbook applied to the BN
sandwich. Gradient support composes via jax.custom_vjp with the
reference composition's VJP (bwd fusion is follow-up work).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_platform import call_by_platform


def _reference_bn_relu_matmul(x, mu, var, gamma, beta, w, eps):
    """Unfused composition (also the custom_vjp's differentiation
    target): y = relu(bn(x)) @ w, plus batch stats of y."""
    xf = x.astype(jnp.float32)
    xhat = (xf - mu) * jax.lax.rsqrt(var + eps)
    a = jax.nn.relu(xhat * gamma + beta).astype(x.dtype)
    y = jnp.dot(a, w, preferred_element_type=jnp.float32)
    s1 = jnp.sum(y, axis=0)
    s2 = jnp.sum(y * y, axis=0)
    return y.astype(x.dtype), s1, s2


def fused_bn_relu_matmul(
    x: jax.Array,          # (M, Cin) raw pre-BN values (bf16/f32)
    mu: jax.Array,         # (Cin,) f32 batch mean of x
    var: jax.Array,        # (Cin,) f32 batch variance of x
    gamma: jax.Array,      # (Cin,) f32
    beta: jax.Array,       # (Cin,) f32
    w: jax.Array,          # (Cin, Cout)
    *,
    eps: float = 1e-5,
    block_m: int = 512,
    block_n: int = 256,
    interpret: Optional[bool] = None,
    accum: str = "scratch",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y, sum(y, 0), sum(y*y, 0)) with y = relu(bn(x)) @ w.

    One pass over x and one write of y; the stats ride the matmul
    epilogue. M and Cout must be multiples of the block sizes (the
    ResNet shapes are).

    accum="scratch" (default): grid is (i, j) with j INNERMOST, so the
    x block's index map is constant across the inner sweep and Pallas
    never re-fetches it — x truly streams ONCE. Stats accumulate in a
    (1, Cout) f32 VMEM scratch (persistent across grid steps on TPU)
    and are written to the outputs exactly once, on the last i row, so
    the revisited-output-block rule is never relied on.

    accum="revisit": grid (j, i) with the reduction dim innermost and
    output-block accumulation — the r4 correctness-fix layout; slower
    (x re-streams once per Cout block) but kept as the
    reference/fallback structure.
    """
    from jax.experimental import pallas as pl

    M, Cin = x.shape
    Cout = w.shape[1]
    block_m = min(block_m, M)
    block_n = min(block_n, Cout)
    if block_n % 128:
        # Mosaic refuses the per-block lane slices below unless they
        # start on a 128-lane boundary ("cannot statically prove that
        # index in dimension 1 is a multiple of 128"); interpret mode
        # never shows it, so say so before any platform is involved.
        raise ValueError(
            f"block_n={block_n} (Cout={Cout}) is not a multiple of 128: "
            "the TPU kernel slices its output-channel blocks on 128-lane "
            "boundaries; use Cout >= 128 and a block_n that is a "
            "multiple of 128")
    if M % block_m or Cout % block_n:
        raise ValueError(f"M={M} / Cout={Cout} not divisible by blocks "
                         f"({block_m}, {block_n})")
    n_i = M // block_m

    def _normalize(x_ref, mu_ref, var_ref, gamma_ref, beta_ref):
        xf = x_ref[...].astype(jnp.float32)
        rs = jax.lax.rsqrt(var_ref[...] + eps)
        return jnp.maximum(
            (xf - mu_ref[...]) * (rs * gamma_ref[...]) + beta_ref[...],
            0.0,
        ).astype(x_ref.dtype)

    if accum == "scratch":
        from jax.experimental.pallas import tpu as pltpu

        last = n_i - 1

        def kernel(x_ref, mu_ref, var_ref, gamma_ref, beta_ref, w_ref,
                   y_ref, s1_ref, s2_ref, s1_acc, s2_acc):
            i = pl.program_id(0)
            j = pl.program_id(1)
            # (A normalize-once VMEM cache of `a` across the j sweep
            # was benchmarked and REJECTED: the scratch store/load
            # costs more than recomputing the prologue at Cin<=256 —
            # 1.36x -> 1.08x on the winning shape — and only lifts the
            # Cin=512 shape to 0.98x, still short of XLA.)
            a = _normalize(x_ref, mu_ref, var_ref, gamma_ref, beta_ref)
            y = jnp.dot(a, w_ref[...],
                        preferred_element_type=jnp.float32)
            y_ref[...] = y.astype(y_ref.dtype)
            part1 = jnp.sum(y, axis=0, keepdims=True)
            part2 = jnp.sum(y * y, axis=0, keepdims=True)
            sl = pl.ds(j * block_n, block_n)

            @pl.when((i == 0) & (i != last))
            def _init():
                s1_acc[:, sl] = part1
                s2_acc[:, sl] = part2

            @pl.when((i != 0) & (i != last))
            def _acc():
                s1_acc[:, sl] += part1
                s2_acc[:, sl] += part2

            @pl.when((i == last) & (i != 0))
            def _final():
                s1_ref[...] = s1_acc[:, sl] + part1
                s2_ref[...] = s2_acc[:, sl] + part2

            @pl.when((i == last) & (i == 0))
            def _single():
                s1_ref[...] = part1
                s2_ref[...] = part2

        grid = (n_i, Cout // block_n)

        def call(interp: bool):
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((block_m, Cin), lambda i, j: (i, 0)),
                    pl.BlockSpec((Cin,), lambda i, j: (0,)),
                    pl.BlockSpec((Cin,), lambda i, j: (0,)),
                    pl.BlockSpec((Cin,), lambda i, j: (0,)),
                    pl.BlockSpec((Cin,), lambda i, j: (0,)),
                    pl.BlockSpec((Cin, block_n), lambda i, j: (0, j)),
                ],
                out_specs=[
                    pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
                    pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
                    pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((M, Cout), x.dtype),
                    jax.ShapeDtypeStruct((1, Cout), jnp.float32),
                    jax.ShapeDtypeStruct((1, Cout), jnp.float32),
                ],
                scratch_shapes=[
                    pltpu.VMEM((1, Cout), jnp.float32),
                    pltpu.VMEM((1, Cout), jnp.float32),
                ],
                interpret=interp,
            )

        y, s1, s2 = call_by_platform(call, x, mu, var, gamma, beta, w,
                                     interpret=interpret)
        return y, s1[0], s2[0]

    def kernel(x_ref, mu_ref, var_ref, gamma_ref, beta_ref, w_ref,
               y_ref, s1_ref, s2_ref):
        # Reduction dim i INNERMOST: Pallas TPU only preserves a
        # revisited output block (s1/s2 depend on j alone) across
        # *consecutive* grid steps — with i outermost the stats would
        # be silently wrong on real TPU whenever Cout > block_n.
        i = pl.program_id(1)
        a = _normalize(x_ref, mu_ref, var_ref, gamma_ref, beta_ref)
        y = jnp.dot(a, w_ref[...], preferred_element_type=jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)
        part1 = jnp.sum(y, axis=0, keepdims=True)
        part2 = jnp.sum(y * y, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _init():
            s1_ref[...] = part1
            s2_ref[...] = part2

        @pl.when(i != 0)
        def _acc():
            s1_ref[...] += part1
            s2_ref[...] += part2

    grid = (Cout // block_n, n_i)

    def call(interp: bool):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, Cin), lambda j, i: (i, 0)),
                pl.BlockSpec((Cin,), lambda j, i: (0,)),
                pl.BlockSpec((Cin,), lambda j, i: (0,)),
                pl.BlockSpec((Cin,), lambda j, i: (0,)),
                pl.BlockSpec((Cin,), lambda j, i: (0,)),
                pl.BlockSpec((Cin, block_n), lambda j, i: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, block_n), lambda j, i: (i, j)),
                pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
                pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((M, Cout), x.dtype),
                jax.ShapeDtypeStruct((1, Cout), jnp.float32),
                jax.ShapeDtypeStruct((1, Cout), jnp.float32),
            ],
            interpret=interp,
        )

    y, s1, s2 = call_by_platform(call, x, mu, var, gamma, beta, w,
                                 interpret=interpret)
    return y, s1[0], s2[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def bn_relu_conv1x1(x, mu, var, gamma, beta, w, eps=1e-5):
    """Differentiable fused op: fwd is the Pallas single-pass kernel,
    bwd is the VJP of the reference composition (XLA-fused; kernel bwd
    is follow-up work). Shapes as fused_bn_relu_matmul."""
    return fused_bn_relu_matmul(x, mu, var, gamma, beta, w, eps=eps)


def _fwd(x, mu, var, gamma, beta, w, eps):
    out = fused_bn_relu_matmul(x, mu, var, gamma, beta, w, eps=eps)
    return out, (x, mu, var, gamma, beta, w)


def _bwd(eps, res, cts):
    x, mu, var, gamma, beta, w = res
    _, vjp = jax.vjp(
        lambda *a: _reference_bn_relu_matmul(*a, eps), x, mu, var, gamma,
        beta, w)
    return vjp(cts)


bn_relu_conv1x1.defvjp(_fwd, _bwd)
