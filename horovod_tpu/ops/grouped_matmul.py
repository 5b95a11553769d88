"""Grouped matrix product for routed experts: rows sorted by expert,
one weight matrix per expert, work in proportion to the rows present.

    out[r] = lhs[r] @ rhs[g]      for the group g that row r belongs to

`lhs` (R, K) holds the rows of group 0 first, then group 1, ...;
`group_sizes` (G,) says how many each has. Their sum may be smaller
than R: the rows behind the last group belong to none, no kernel visits
them, and what comes back in them, forward and backward, is whatever
the buffer held: a caller reads them by mistake only. So a dispatch
buffer sized for the worst routing costs memory but no product, and no
pass over it to write zeros. `rhs` is (G, K, N).

The kernels are jax's own Pallas TPU "megablox" (`gmm`, `tgmm`): the
grid's row dimension is the number of (row tile, group) pairs that hold
rows, computed on the device from `group_sizes`, so nothing depends on
the routing at compile time. This file adds what the model needs around
them: the interpret-mode rule of ops/pallas_platform.py, tile sizes from
the shapes, and a vjp whose three products each get tiles that fit their
own shapes (megablox's own vjp hands one tiling to all three).
"""
from __future__ import annotations

import functools
import importlib
import math

import jax

# The package exports its custom-vjp `gmm` under the module's own name.
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

from .pallas_platform import call_by_platform

# Tile limits: rows of one tile (a group of 256 rows then takes one or
# two), and the contraction / output widths of one tile (VMEM: an f32
# accumulator and double-buffered inputs of 1024 x 1024 fit in 16 MiB).
TILE_ROWS = 256
TILE_WIDTH = 1024


def _tile(width: int) -> int:
    """The largest multiple of 128 up to TILE_WIDTH that divides
    `width`; the whole width where it is small or has no such divisor."""
    if width <= TILE_WIDTH:
        return width
    for tile in range(TILE_WIDTH, 127, -128):
        if width % tile == 0:
            return tile
    return TILE_WIDTH


def _tiling(rows: int, k: int, n: int) -> tuple:
    return math.gcd(rows, TILE_ROWS), _tile(k), _tile(n)


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]

    def call(interpret: bool):
        return functools.partial(
            _megablox.gmm, preferred_element_type=lhs.dtype,
            tiling=_tiling(lhs.shape[0], lhs.shape[1], n),
            transpose_rhs=transpose_rhs, interpret=interpret)

    return call_by_platform(call, lhs, rhs, group_sizes)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """(R, K) x (G, K, N) -> (R, N) by `group_sizes` (G,) int32; see the
    module docstring. `lhs` and `rhs` share a dtype; the product
    accumulates in float32."""
    return _gmm(lhs, rhs, group_sizes, False)


def _fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes, False), (lhs, rhs, group_sizes)


def _bwd(residuals, g):
    lhs, rhs, group_sizes = residuals
    d_lhs = _gmm(g, rhs, group_sizes, True)

    def call(interpret: bool):
        return functools.partial(
            _megablox.tgmm, preferred_element_type=rhs.dtype,
            tiling=_tiling(lhs.shape[0], lhs.shape[1], g.shape[1]),
            interpret=interpret)

    d_rhs = call_by_platform(call, lhs.swapaxes(0, 1), g, group_sizes)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_fwd, _bwd)
