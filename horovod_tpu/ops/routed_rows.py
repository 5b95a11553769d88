"""Data movement of a routed-expert layer whose cost follows the rows
that hold a (token, expert) pair, not the dispatch buffer.

The buffer of `models/latent_moe.py::held_experts` has a row for every
pair, the pairs held here sorted to its front by expert, each expert's
rows in token order; `held_rows = sum(sizes)` of them hold a pair and
that count is known on the device only. Three things move rows, forward
and backward, and each is a Pallas kernel whose work is set by that
count, with no device control flow outside the kernels:

* **buffer-major** (`_gather_rows`, `_combine_bwd_rows`): the grid runs
  over the row tiles that hold a pair, `row_tiles(held_rows)` of them,
  a grid extent computed on the device as megablox's is. A tile's
  source rows are token rows at any place, fetched one DMA a row. A
  single row of a 2-D array is not addressable (its 8 x 128 tiles hold
  8 rows), so a token-sized source is handed over as (T, D / 128, 128),
  a row a tile group; the copy that makes it is token-sized.
* **token-major** (`_sum_rows`): the grid runs over blocks of tokens,
  and a block's rows in the buffer are, for each expert, one contiguous
  run (an expert's rows are in token order). A block fetches the
  aligned 16-row chunks that cover its runs, two waves in flight, and
  adds each row that belongs to it to its token, in float32.
  `TokenPlan` lists the chunks and says whose each of their rows is; it
  is integer arithmetic on per-block counts, no sort and no scatter.
* **the activation between the grouped products** (`gated_activation`),
  elementwise over the same row tiles.

Rows behind `held_rows` are never written by any of these and never
read as numbers: a tile's tail behind the count holds what the memory
held. What still scales with the buffer: its allocation, the sort of
the pairs into expert order (`route`; the gates ride on it), one more
in the backward pass that takes the gates' gradients back to pair
order, and integer vectors of its length.

`dispatch`, `combine` and `gated_activation` carry the derivatives;
kernels are interpreted where lowered for the CPU
(ops/pallas_platform.py).

**A Pallas entry is a module-level `jax.jit`** (`_route` and the five
`_*_rows` / `_silu_gate*` functions below): the host traces a kernel,
both branches of the platform rule and every nested loop body, once per
shape (and mesh in scope) in a process, however many layers call it,
forward, recomputed and backward, and lowers it to one private function
per program, one more where a block recomputes it. The derivative rules
stay outside the jits: differentiation sees one opaque call. Called
without a jit at each site, five applications of the layer cost the
set-up of its benchmark cell 16,500 traces and 28 s in every process
(PERF.md section 6, PR 33 / 34).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_platform import call_by_platform

# Rows of one buffer-major tile, and the granule the buffer's rows are
# padded to (a 1-D int32 block in SMEM takes multiples of 1024).
ROW_TILE = 256
ROW_GRANULE = 1024
# Rows of one aligned chunk of the token-major fetch (a packed bf16
# tile), chunks of one wave, tokens of one block.
CHUNK = 16
WAVE = 16
TOKEN_BLOCK = 256
LANES = 128


def padded_rows(pairs: int) -> int:
    """Rows of the dispatch buffer for `pairs` (token, expert) pairs:
    whole granules (as every list a kernel reads by block from SMEM)."""
    return -(-pairs // ROW_GRANULE) * ROW_GRANULE


def row_tiles(held_rows):
    """The extent every buffer-major kernel here shares: row tiles that
    hold a pair, from the (1,) int32 count on the device."""
    return (held_rows[0] + ROW_TILE - 1) // ROW_TILE


def _lane_split(width: int) -> tuple:
    """(groups, lanes): a row of `width` as whole lane tiles. (Compiled
    for the chip, a row's DMA wants whole (8, 128) tiles: `_landing`
    pads the groups. Narrower rows run interpreted only.)"""
    lanes = LANES if width % LANES == 0 else width
    return width // lanes, lanes


def _token_block(tokens: int) -> int:
    block = math.gcd(tokens, TOKEN_BLOCK)
    return block if block % 8 == 0 else tokens


class TokenPlan(NamedTuple):
    """What a block of tokens fetches from the buffer (module
    docstring): `chunks` (blocks,) how many aligned chunks, `source`
    (blocks, slots) which, `pair` (blocks, slots x CHUNK) for each
    fetched row its (token, expert) pair counted from the block's
    first, -1 where the row is not the block's."""

    chunks: jax.Array
    source: jax.Array
    pair: jax.Array


class Routing(NamedTuple):
    """A routing of (T, k) pairs as `held_experts` sorts it: `order`
    the pair at each buffer row, `here` (T, k) whether a pair is held,
    `sizes` rows per expert, `held_rows` (1,) their sum; by buffer row,
    (padded,): `token` the row's token and `gate` its pair's gate (a
    constant: the gates' gradient is `combine`'s)."""

    order: jax.Array
    here: jax.Array
    sizes: jax.Array
    held_rows: jax.Array
    token: jax.Array
    gate: jax.Array
    plan: TokenPlan


def route(key, gates, held: int) -> Routing:
    """`key` (T k,) int32: the held expert of each pair, `held` for a
    pair not held here; `gates` (T, k). One sort, the gates riding on
    it; the rest is arithmetic on per-block counts and gathers of whole
    chunks."""
    # (Stopped out here: the jit then sees no tangent to carry.)
    return _route(key, jax.lax.stop_gradient(gates), held=held)


@functools.partial(jax.jit, static_argnames="held")
def _route(key, gates, *, held: int) -> Routing:
    pairs = key.shape[0]
    tokens, k = gates.shape
    block = _token_block(tokens)
    blocks, per_block = tokens // block, block * k
    _, order, gate = jax.lax.sort(
        (key, jnp.arange(pairs, dtype=jnp.int32),
         gates.reshape(pairs).astype(jnp.float32)),
        num_keys=1, is_stable=True)
    counts = jnp.sum(
        key.reshape(blocks, per_block, 1) == jnp.arange(held), axis=1,
        dtype=jnp.int32)                                  # (blocks, held)
    sizes = counts.sum(0)
    by_row = jnp.pad(order, (0, padded_rows(pairs) - pairs))

    # A block's run in expert e: rows [lo, hi) of the buffer.
    lo = (jnp.cumsum(sizes) - sizes)[None] + jnp.cumsum(counts, 0) - counts
    hi = lo + counts
    first = lo // CHUNK
    covering = jnp.where(counts > 0, (hi - 1) // CHUNK - first + 1, 0)
    ends = jnp.cumsum(covering, axis=1)
    # A run of n rows lies in at most (n + 14) // 16 + 1 chunks.
    slots = -(-(per_block // CHUNK + 2 * held) // 64) * 64
    slot = jnp.arange(slots, dtype=jnp.int32)
    # The expert whose run slot s covers; `of(a)` is a[block, expert of
    # s], (blocks, slots), picked without a gather.
    expert = jnp.sum(ends[:, None, :] <= slot[None, :, None], axis=2)
    picks = jnp.minimum(expert, held - 1)[:, :, None] == jnp.arange(held)
    of = lambda a: jnp.sum(jnp.where(picks, a[:, None, :], 0), axis=2)
    live = slot[None] < ends[:, -1:]
    source = jnp.where(live, of(first) + slot[None] - of(ends - covering), 0)
    row = source[:, :, None] * CHUNK + jnp.arange(CHUNK)
    mine = (live[:, :, None] & (row >= of(lo)[:, :, None])
            & (row < of(hi)[:, :, None]))
    starts = jnp.arange(blocks, dtype=jnp.int32)[:, None, None] * per_block
    pair = jnp.where(mine, by_row.reshape(-1, CHUNK)[source] - starts, -1)
    # (1-D blocks in SMEM: a block's lists start at multiples of 1024.)
    source = jnp.pad(source, ((0, 0), (0, padded_rows(slots) - slots)))
    plan = TokenPlan(ends[:, -1], source,
                     pair.reshape(blocks, slots * CHUNK))
    return Routing(order, (key < held).reshape(tokens, k), sizes,
                   jnp.sum(sizes)[None], by_row // k,
                   jnp.pad(gate, (0, by_row.shape[0] - pairs)), plan)


# ------------------------------------------------------------ buffer-major

def _tile_in_granule():
    """Which of its granule's row tiles this grid step is: what comes in
    by granule (SMEM lists, lane-dense vectors) is read from there."""
    return pl.program_id(0) % (ROW_GRANULE // ROW_TILE)


def _fetch_rows(token_ref, held_ref, source_ref, landing, semaphore):
    """Start and await one DMA a row of this tile that holds a pair:
    `landing[r] = source[token of row r]`."""
    offset = _tile_in_granule() * ROW_TILE
    rows = jnp.minimum(ROW_TILE, held_ref[0] - pl.program_id(0) * ROW_TILE)

    def copy(r):
        return pltpu.make_async_copy(
            source_ref.at[token_ref[offset + r]], landing.at[r], semaphore)

    jax.lax.fori_loop(0, rows, lambda r, c: copy(r).start() or c, 0)
    jax.lax.fori_loop(0, rows, lambda r, c: copy(r).wait() or c, 0)


# Block specs of a buffer-major call behind its scalar prefetch
# (`held_rows`): a granule's tokens in SMEM, a (ROW_TILE, width) block.
_TOKENS_SPEC = pl.BlockSpec(
    (ROW_GRANULE,), lambda i, n: (i // (ROW_GRANULE // ROW_TILE),),
    memory_space=pltpu.SMEM)


def _row_block(width: int):
    return pl.BlockSpec((ROW_TILE, width), lambda i, n: (i, 0))


def _as_row_tiles(x):
    groups, lanes = _lane_split(x.shape[1])
    return _whole_groups(x.reshape(x.shape[0], groups, lanes))


def _gather_kernel(held_ref, token_ref, source_ref, out_ref, landing,
                   semaphore):
    _fetch_rows(token_ref, held_ref, source_ref, landing, semaphore)
    groups, lanes = out_ref.shape[1] // landing.shape[2], landing.shape[2]
    for c in range(groups):
        out_ref[:, c * lanes:(c + 1) * lanes] = landing[:, c, :]


@jax.jit
def _gather_rows(held_rows, token, source):
    """(padded, D): row p < held_rows is `source[token[p]]`."""
    width = source.shape[1]

    def call(interpret: bool):
        return pl.pallas_call(
            _gather_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(row_tiles(held_rows),),
                in_specs=[_TOKENS_SPEC, pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=_row_block(width),
                scratch_shapes=[
                    pltpu.VMEM((ROW_TILE, *_landing(width)), source.dtype),
                    pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct(
                (token.shape[0], width), source.dtype),
            interpret=interpret, name="routed_gather_rows")

    return call_by_platform(call, held_rows, token, _as_row_tiles(source))


def _turn(vector, to_column: bool):
    """A (1, 128) row as a (128, 1) column or back, exactly: the
    diagonal of its broadcast, summed along the other axis."""
    n = LANES
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    spread = jnp.where(eye, jnp.broadcast_to(vector, (n, n)), 0)
    return jnp.sum(spread, axis=1 if to_column else 0, keepdims=True)


def _combine_bwd_kernel(held_ref, token_ref, g_ref, y_ref, gate_ref,
                        d_y_ref, d_gate_ref, landing, semaphore):
    _fetch_rows(token_ref, held_ref, g_ref, landing, semaphore)
    groups, lanes = y_ref.shape[1] // landing.shape[2], landing.shape[2]
    dtype = d_y_ref.dtype
    # Gates and their gradients travel a row of 128 to a sublane: this
    # tile's are sublanes `first` onward of a block of ROW_GRANULE rows.
    first = _tile_in_granule() * (ROW_TILE // LANES)
    for part in range(ROW_TILE // LANES):
        rows = slice(part * LANES, (part + 1) * LANES)
        gate = _turn(gate_ref[pl.ds(first + part, 1), :], True)
        gate = gate.astype(dtype).astype(jnp.float32)
        dot = jnp.zeros((LANES, lanes), jnp.float32)
        for c in range(groups):
            cols = slice(c * lanes, (c + 1) * lanes)
            g = landing[rows, c, :]
            d_y_ref[rows, cols] = (
                g.astype(dtype).astype(jnp.float32) * gate).astype(dtype)
            dot = dot + y_ref[rows, cols].astype(jnp.float32) * g
        d_gate_ref[pl.ds(first + part, 1), :] = _turn(
            jnp.sum(dot, axis=1, keepdims=True), False)


@jax.jit
def _combine_bwd_rows(held_rows, token, gate, g, y):
    """From the float32 gradient `g` (T, D) of the combined tokens:
    `d_y[p] = gate[p] x g[token[p]]` in y's dtype and `d_gate[p] =
    <y[p], g[token[p]]>` (padded,) float32, for p < held_rows."""
    rows, width = y.shape
    by_lane = pl.BlockSpec(
        (ROW_GRANULE // LANES, LANES),
        lambda i, n: (i // (ROW_GRANULE // ROW_TILE), 0))

    def call(interpret: bool):
        return pl.pallas_call(
            _combine_bwd_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(row_tiles(held_rows),),
                in_specs=[_TOKENS_SPEC, pl.BlockSpec(memory_space=pl.ANY),
                          _row_block(width), by_lane],
                out_specs=[_row_block(width), by_lane],
                scratch_shapes=[
                    pltpu.VMEM((ROW_TILE, *_landing(width)), jnp.float32),
                    pltpu.SemaphoreType.DMA(())]),
            out_shape=[jax.ShapeDtypeStruct((rows, width), y.dtype),
                       jax.ShapeDtypeStruct((rows // LANES, LANES),
                                            jnp.float32)],
            interpret=interpret, name="routed_combine_bwd_rows")

    d_y, d_gate = call_by_platform(
        call, held_rows, token, _as_row_tiles(g), y,
        gate.reshape(rows // LANES, LANES))
    return d_y, d_gate.reshape(rows)


# ------------------------------------------------------------- token-major

def _sum_kernel(chunks_ref, source_ref, pair_ref, weight_ref, rows_ref,
                out_ref, landing, total, semaphores, *, k: int):
    block = pl.program_id(0)
    chunks = chunks_ref[block]
    waves = (chunks + WAVE - 1) // WAVE

    def copies(wave, do):
        slot = wave % 2

        def one(c, carry):
            row = pl.multiple_of(source_ref[wave * WAVE + c] * CHUNK,
                                 CHUNK)
            do(pltpu.make_async_copy(
                rows_ref.at[pl.ds(row, CHUNK), :],
                landing.at[slot, pl.ds(pl.multiple_of(c * CHUNK, CHUNK),
                                       CHUNK), :],
                semaphores.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(WAVE, chunks - wave * WAVE), one, 0)

    total[...] = jnp.zeros_like(total)

    @pl.when(chunks > 0)
    def _():
        copies(0, lambda copy: copy.start())

    def wave_body(wave, carry):
        @pl.when(wave + 1 < waves)
        def _():
            copies(wave + 1, lambda copy: copy.start())

        copies(wave, lambda copy: copy.wait())

        def chunk_body(c, carry):
            rows = landing[wave % 2, pl.ds(pl.multiple_of(c * CHUNK, CHUNK),
                                           CHUNK), :].astype(jnp.float32)
            base = (wave * WAVE + c) * CHUNK
            for r in range(CHUNK):
                pair = pair_ref[base + r]

                @pl.when(pair >= 0)
                def _():
                    total[pl.ds(pair // k, 1), :] += (
                        rows[r:r + 1, :] * weight_ref[pair])
            return carry

        jax.lax.fori_loop(0, jnp.minimum(WAVE, chunks - wave * WAVE),
                          chunk_body, 0)
        return carry

    jax.lax.fori_loop(0, waves, wave_body, 0)
    out_ref[...] = total[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames="dtype")
def _sum_rows(rows, weight, plan: TokenPlan, *, dtype):
    """(T, D) `dtype`: each token's sum, in float32, of `weight` x its
    held pairs' rows of `rows` (padded, D); `weight` (T, k) float32."""
    width = rows.shape[1]
    tokens, k = weight.shape
    blocks = plan.chunks.shape[0]
    block = tokens // blocks
    weight = weight.reshape(blocks, block * k)
    weight = jnp.pad(weight, ((0, 0), (0, padded_rows(block * k) - block * k)))
    scalars = lambda a: pl.BlockSpec((a.shape[1],), lambda b, c: (b,),
                                     memory_space=pltpu.SMEM)

    def call(interpret: bool):
        return pl.pallas_call(
            functools.partial(_sum_kernel, k=k),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(blocks,),
                in_specs=[scalars(plan.source), scalars(plan.pair),
                          scalars(weight),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((block, width), lambda b, c: (b, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, WAVE * CHUNK, width), rows.dtype),
                    pltpu.VMEM((block, width), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
            interpret=interpret, name="routed_sum_rows")

    return call_by_platform(call, plan.chunks, plan.source.reshape(-1),
                            plan.pair.reshape(-1), weight.reshape(-1), rows)


# ------------------------------------------------------ the layer's two ends

@jax.custom_vjp
def dispatch(tokens, routing: Routing):
    """The dispatch buffer (padded, D): row p < held_rows holds the
    token of the p-th pair in expert order. Backward: a token's gradient
    is the float32 sum of its held pairs' rows."""
    return _gather_rows(routing.held_rows, routing.token, tokens)


def _dispatch_fwd(tokens, routing):
    return dispatch(tokens, routing), routing


def _dispatch_bwd(routing, g):
    ones = jnp.ones(routing.here.shape, jnp.float32)
    return _sum_rows(g, ones, routing.plan, dtype=g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, gates, routing: Routing):
    """(T, D) float32: sum over a token's held pairs of gate x the
    pair's row of `y` (padded, D); `gates` (T, k) float32. Backward, from
    `y` itself and the token gradient's rows fetched by pair: row p's
    gradient is its pair's gate x its token's gradient, a gate's the
    inner product of its pair's row with its token's gradient."""
    return _sum_rows(y, gates, routing.plan, dtype=jnp.float32)


def _combine_fwd(y, gates, routing):
    return combine(y, gates, routing), (y, routing)


def _combine_bwd(residuals, g):
    y, routing = residuals
    d_y, d_gate = _combine_bwd_rows(routing.held_rows, routing.token,
                                    routing.gate, g, y)
    # By pair again: `order` is a permutation, sorting by it undoes it.
    _, d_gates = jax.lax.sort(
        (routing.order, d_gate[:routing.order.shape[0]]), num_keys=1)
    d_gates = d_gates.reshape(routing.here.shape)
    return d_y, jnp.where(routing.here, d_gates, 0), None


combine.defvjp(_combine_fwd, _combine_bwd)


# ------------------------------------------------------------ the activation

def _silu_gate_kernel(held_ref, h_ref, out_ref):
    f = out_ref.shape[1]
    gate = h_ref[:, :f].astype(jnp.float32)
    up = h_ref[:, f:].astype(jnp.float32)
    out_ref[...] = (gate * jax.nn.sigmoid(gate) * up).astype(out_ref.dtype)


def _silu_gate_bwd_kernel(held_ref, h_ref, g_ref, d_h_ref):
    f = g_ref.shape[1]
    gate = h_ref[:, :f].astype(jnp.float32)
    up = h_ref[:, f:].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    d_h_ref[:, :f] = (g * up * sig * (1 + gate * (1 - sig))).astype(
        d_h_ref.dtype)
    d_h_ref[:, f:] = (g * gate * sig).astype(d_h_ref.dtype)


def _over_row_tiles(kernel, name: str, held_rows, out_width: int, *arrays):
    """`kernel` on the row tiles before `held_rows` of `arrays`, each
    (padded, its width); (padded, out_width) in the first one's dtype."""
    rows, dtype = arrays[0].shape[0], arrays[0].dtype

    def call(interpret: bool):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(row_tiles(held_rows),),
                in_specs=[_row_block(a.shape[1]) for a in arrays],
                out_specs=_row_block(out_width)),
            out_shape=jax.ShapeDtypeStruct((rows, out_width), dtype),
            interpret=interpret, name=name)

    return call_by_platform(call, held_rows, *arrays)


@jax.jit
def _silu_gate(held_rows, h):
    return _over_row_tiles(_silu_gate_kernel, "routed_gated_activation",
                           held_rows, h.shape[1] // 2, h)


@jax.jit
def _silu_gate_bwd(held_rows, h, g):
    return _over_row_tiles(_silu_gate_bwd_kernel,
                           "routed_gated_activation_bwd", held_rows,
                           h.shape[1], h, g)


@jax.custom_vjp
def gated_activation(h, held_rows):
    """`silu(h[:, :F]) * h[:, F:]` (padded, F) on the rows before
    `held_rows` (1,), computed in float32 and rounded once."""
    return _silu_gate(held_rows, h)


def _gated_activation_fwd(h, held_rows):
    return gated_activation(h, held_rows), (h, held_rows)


def _gated_activation_bwd(residuals, g):
    h, held_rows = residuals
    return _silu_gate_bwd(held_rows, h, g), None


gated_activation.defvjp(_gated_activation_fwd, _gated_activation_bwd)


def _landing(width: int) -> tuple:
    """(groups, lanes) of a token's row as the buffer-major kernels land
    it: the lane tiles of `_lane_split` padded to a multiple of 8, the
    rows of a whole (8, 128) tile, which a DMA of one row needs compiled
    for the chip (a width of 2304 is 18 lane tiles, landed as 24; 2048
    is 16 already). Narrower rows run interpreted only and stay as they
    are."""
    groups, lanes = _lane_split(width)
    return (-(-groups // 8) * 8 if lanes == LANES else groups), lanes


def _whole_groups(x):
    """A token-sized source (T, groups, lanes) padded with zeros to the
    groups `_landing` gives; as it is where it has them already."""
    groups = _landing(x.shape[1] * x.shape[2])[0]
    if groups == x.shape[1]:
        return x
    return jnp.pad(x, ((0, 0), (0, groups - x.shape[1]), (0, 0)))
