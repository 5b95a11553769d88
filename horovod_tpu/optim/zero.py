"""ZeRO-1/2 sharded optimizer state across both data planes (ROADMAP
item 3; Rajbhandari et al., "ZeRO: Memory Optimizations Toward Training
Trillion Parameter Models"; docs/running.md "ZeRO sharded optimizer
state").

`DistributedOptimizer(zero=1|2)` stops keeping a full replica of the
inner optimizer's state (Adam moments etc.) on every data rank. Instead
each rank owns a contiguous shard of the FLATTENED state and the update
becomes reduce-scatter → shard update → allgather:

* **Traced plane** (inside jit/shard_map over the resolved data axis —
  the `hvd.resolve_axis` rule): gradients flatten into one accumulation
  buffer, `lax.psum_scatter` reduces it and leaves each device exactly
  its owned 1/n slice (the wire never carries the full gradient twice —
  ZeRO-2's gradient sharding falls out of the lowering), the inner
  optimizer updates that slice only, and `lax.all_gather` rebuilds the
  full update. Every `ZeroState` leaf carries a leading shard dimension
  (per-device `(1, ...)`, global `(n, ...)`), so one uniform
  `PartitionSpec(axis)` prefix shards the whole state tree — the
  NamedSharding idiom that scales to pod meshes — and the global state
  is an ordinary sharded jax.Array that `JaxState`/`CheckpointManager`
  snapshot unchanged.
* **Eager plane** (process mode): leaf ownership is the
  `shard_ranges` balanced-by-bytes cut from common/checkpoint.py —
  the same deterministic tiling the checkpoint writer uses — over the
  gradient leaves; grads ride the engine's grouped allreduce (native
  kernels, wire codecs and the engine's own error feedback apply), the
  owned leaves update as one flat accumulation segment, and the
  updated segments allgather back (raw full-width floats, so
  reassembly is bitwise).

**Error feedback as optimizer state** (Karimireddy et al. 2019): with
`error_feedback=True` the traced wire cast (PR 15's stateless bf16/fp16
cast, plus the int8-with-scale lane) gains the eager codec's accuracy
story — the quantization residual `e - decode(encode(e))` is carried in
`ZeroState.residual` across steps and added back before the next
encode, so the shipped values telescope to the true sum. Under ZeRO the
residual lives on the allgather (update) leg and is sharded with the
moments — 1/n memory — while the scatter leg keeps the stateless cast
(its input is the full local gradient, so a residual there would cost
full-gradient memory, defeating ZeRO). Without ZeRO the residual is
gradient-sized and corrects the allreduce itself.

Supported inner optimizers: elementwise transforms (sgd, momentum,
adam(w), rmsprop, ...). Transforms that need cross-tree statistics
(e.g. `clip_by_global_norm`) see only the local shard here — apply
them outside the wrapper.
"""
from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from ..common import basics, telemetry
from ..common.checkpoint import shard_ranges
from ..common.types import ReduceOp
from ..utils.compat import axis_index as _axis_index, axis_size as _axis_size

_STATE_BYTES_HELP = (
    "Optimizer-state bytes this rank holds: mode=\"sharded\" is the "
    "measured owned-shard footprint, mode=\"replicated\" is what a "
    "full-replica optimizer would hold (docs/running.md \"ZeRO sharded "
    "optimizer state\")")


class ZeroState(NamedTuple):
    """Traced-plane optimizer state.

    ``inner`` — the inner optimizer's state over the owned flat shard;
    under ZeRO every leaf carries a leading shard dim (per-device
    ``(1, ...)``, global ``(n, ...)``) so a uniform ``P(axis)`` prefix
    spec shards the whole tree. In EF-only mode (``zero=0``) ``inner``
    is the unmodified full-tree state (replicated, spec ``P()``).

    ``residual`` — the error-feedback residual, ``(1, k)`` per device
    over the owned update shard (ZeRO) or ``(1, total)`` over the flat
    gradient buffer (EF-only); ``None`` when error feedback is off, so
    disabled mode carries zero extra leaves.
    """

    inner: Any
    residual: Optional[Any]


@jax.tree_util.register_pytree_node_class
class ZeroEagerState:
    """Eager-plane (process mode) state: the inner optimizer's state
    over this rank's flat owned segment, plus the static leaf-range
    cut ``[lo, hi)`` of ``shard_ranges(leaf_bytes, nshards)`` it was
    built from (aux data, not leaves — checkpoint trees stay
    arrays-only)."""

    def __init__(self, inner, lo: int, hi: int, nshards: int):
        self.inner = inner
        self.lo = int(lo)
        self.hi = int(hi)
        self.nshards = int(nshards)

    def tree_flatten(self):
        return (self.inner,), (self.lo, self.hi, self.nshards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"ZeroEagerState(leaves[{self.lo}:{self.hi}] of "
                f"{self.nshards} shards)")


# -- shared flatten/pack helpers ---------------------------------------
def _is_tracer(x) -> bool:
    try:
        return isinstance(x, jax.core.Tracer)
    except Exception:  # pragma: no cover
        return False


def _acc_dtype(leaves):
    """The accumulation dtype of the flat buffer: the widest leaf dtype
    (the grouped_allreduce convention)."""
    return jnp.result_type(*[jnp.asarray(l).dtype for l in leaves])


def _metas(leaves):
    return [(np.shape(l), int(np.prod(np.shape(l), dtype=np.int64)),
             jnp.asarray(l).dtype) for l in leaves]


def _pack(leaves, acc):
    return jnp.concatenate([jnp.ravel(jnp.asarray(l)).astype(acc)
                            for l in leaves]) if leaves else jnp.zeros(
                                (0,), acc)


def _unpack(flat, metas):
    out, off = [], 0
    for shape, size, dt in metas:
        out.append(jnp.reshape(flat[off:off + size], shape).astype(dt))
        off += size
    return out


def _state_nbytes(tree) -> int:
    return sum(int(np.prod(np.shape(l), dtype=np.int64))
               * jnp.asarray(l).dtype.itemsize
               for l in jax.tree.leaves(tree))


def _abstract_nbytes(tree) -> int:
    return sum(int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def _replicated_state_bytes(inner, params) -> int:
    """What a full-replica inner optimizer would hold per rank —
    measured abstractly (jax.eval_shape costs no memory)."""
    try:
        return _abstract_nbytes(jax.eval_shape(inner.init, params))
    except Exception:  # pragma: no cover - exotic inner transforms
        return 0


# -- telemetry / status -------------------------------------------------
_status_lock = threading.Lock()
_status: dict = {}


def _note_status(**kw):
    """Record the live ZeRO configuration for the `/status` `zero`
    section (consumed by engine.status(), rendered by hvdtop)."""
    with _status_lock:
        _status.update(kw)
        _status["wall"] = time.time()


def status_snapshot() -> dict:
    """The `zero` section of `/status`; {} until a ZeRO/EF optimizer
    initializes in this process."""
    with _status_lock:
        return dict(_status)


def _set_state_gauges(sharded: int, replicated: int):
    telemetry.gauge("horovod_optimizer_state_bytes", _STATE_BYTES_HELP,
                    labels={"mode": "sharded"}).set(int(sharded))
    telemetry.gauge("horovod_optimizer_state_bytes", _STATE_BYTES_HELP,
                    labels={"mode": "replicated"}).set(int(replicated))


# -- traced plane -------------------------------------------------------
def _update_wire_mode(x) -> Optional[str]:
    """Codec decision for the allgather (update) leg: same gates as the
    gradient-side policy — int8 lane first (opt-in), then the bf16/fp16
    cast — on fp32 payloads at or above the min-bytes floor. Trace-time
    like every traced knob."""
    from ..ops.traced import _traced_int8_enabled, _traced_wire_dtype

    if _traced_int8_enabled(x, ReduceOp.SUM):
        return "int8"
    dt = _traced_wire_dtype(x, ReduceOp.SUM)
    if dt is not None:
        return "fp16" if dt == jnp.float16 else "bf16"
    return None


def _encode_gather(h, ax, n):
    """Encode the owned update shard for the allgather leg, gather, and
    decode — returns (full updates buffer, this device's decoded own
    contribution) so the caller can form the EF residual. The decode of
    the own shard is BITWISE what every receiver computes for it, so
    the residual accounts exactly the shipped error."""
    mode = _update_wire_mode(h)
    if mode == "int8":
        from ..ops.traced import int8_encode

        q, scale = int8_encode(h.astype(jnp.float32))
        qs = lax.all_gather(q, ax, tiled=True)           # (n·k,) int8
        ss = lax.all_gather(scale, ax)                   # (n,) fp32
        k = h.shape[0]
        full = (qs.astype(jnp.float32).reshape(n, k)
                * ss[:, None]).reshape(n * k).astype(h.dtype)
        dec_own = (q.astype(jnp.float32) * scale).astype(h.dtype)
        return full, dec_own
    if mode in ("bf16", "fp16"):
        dt = jnp.float16 if mode == "fp16" else jnp.bfloat16
        w = h.astype(dt)
        return (lax.all_gather(w, ax, tiled=True).astype(h.dtype),
                w.astype(h.dtype))
    return lax.all_gather(h, ax, tiled=True), h


def _shard_geometry(total: int, n: int):
    pad = (-total) % n
    return pad, (total + pad) // n


def _traced_zero_init(inner, params_leaves, ax, error_feedback: bool):
    n = _axis_size(ax)
    idx = _axis_index(ax)
    acc = _acc_dtype(params_leaves)
    flat_p = _pack(params_leaves, acc)
    pad, k = _shard_geometry(flat_p.shape[0], n)
    if pad:
        flat_p = jnp.pad(flat_p, (0, pad))
    p_shard = lax.dynamic_slice(flat_p, (idx * k,), (k,))
    st = jax.tree.map(lambda l: jnp.asarray(l)[None], inner.init(p_shard))
    res = jnp.zeros((1, k), acc) if error_feedback else None
    return ZeroState(st, res)


def _traced_zero_update(inner, state, grads, params, ax, op, prescale,
                        postscale, error_feedback: bool, extra):
    from ..ops.traced import _scale, _traced_wire_dtype

    g_leaves, treedef = jax.tree.flatten(grads)
    p_leaves = jax.tree.leaves(params)
    metas = _metas(g_leaves)
    acc = _acc_dtype(g_leaves)
    n = _axis_size(ax)
    idx = _axis_index(ax)

    flat_g = _scale(_pack(g_leaves, acc), prescale)
    total = flat_g.shape[0]
    pad, k = _shard_geometry(total, n)
    if pad:
        flat_g = jnp.pad(flat_g, (0, pad))
    # Scatter leg: the reduce-scatter IS the gradient reduction — each
    # device receives only its owned 1/n slice (ZeRO-2's gradient
    # sharding). Without error feedback the stateless wire cast applies
    # exactly as the PR 15 allreduce policy does. WITH error feedback
    # the scatter leg ships full width and the whole compression budget
    # moves to the allgather leg below: a scatter-side residual would
    # be full-gradient-sized (the cast error is per-contributor,
    # pre-reduction), while the allgather-side residual is the owned
    # (k,) shard — the only leg correctable at 1/n memory.
    wire_dt = None if error_feedback else _traced_wire_dtype(flat_g, op)
    if wire_dt is not None:
        g_shard = lax.psum_scatter(
            flat_g.astype(wire_dt), ax, scatter_dimension=0, tiled=True,
        ).astype(acc)
    else:
        g_shard = lax.psum_scatter(flat_g, ax, scatter_dimension=0,
                                   tiled=True)
    if op == ReduceOp.AVERAGE:
        g_shard = g_shard / n
    g_shard = _scale(g_shard, postscale)

    flat_p = _pack(p_leaves, acc)
    if pad:
        flat_p = jnp.pad(flat_p, (0, pad))
    p_shard = lax.dynamic_slice(flat_p, (idx * k,), (k,))

    inner_state = jax.tree.map(lambda l: l[0], state.inner)
    upd_shard, new_inner = inner.update(g_shard, inner_state, p_shard,
                                        **extra)

    # Allgather leg, with the sharded EF residual: h = update + carry;
    # ship encode(h); next step's carry is h - decode(encode(h)).
    if error_feedback:
        h = upd_shard + state.residual[0]
    else:
        h = upd_shard
    full, dec_own = _encode_gather(h, ax, n)
    new_res = (h - dec_own) if error_feedback else None
    if pad:
        full = full[:total]
    updates = jax.tree.unflatten(treedef, _unpack(full, metas))
    new_state = ZeroState(
        jax.tree.map(lambda l: l[None], new_inner),
        new_res[None] if error_feedback else None)
    return updates, new_state


def _traced_ef_init(inner, params_leaves, params, ax):
    """EF without ZeRO: full inner state (replicated), plus a
    per-device residual over the whole flat gradient buffer."""
    total = sum(int(np.prod(np.shape(l), dtype=np.int64))
                for l in params_leaves)
    acc = _acc_dtype(params_leaves)
    return ZeroState(inner.init(params),
                     jnp.zeros((1, total), acc))


def _traced_ef_update(inner, state, grads, params, ax, op, prescale,
                      postscale, extra):
    """EF-only traced allreduce: the stateless wire cast becomes
    cast-with-carry — e = grads + residual is encoded, the psum ships
    the encoded values, and the new residual is e - decode(encode(e)),
    so the summed wire values telescope to the true gradient sum."""
    from ..ops.traced import (
        _scale,
        _traced_int8_enabled,
        _traced_wire_dtype,
        int8_encode,
    )

    g_leaves, treedef = jax.tree.flatten(grads)
    metas = _metas(g_leaves)
    acc = _acc_dtype(g_leaves)
    n = _axis_size(ax)
    flat = _scale(_pack(g_leaves, acc), prescale)
    e = flat + state.residual[0]
    if _traced_int8_enabled(e, op):
        q, scale = int8_encode(e.astype(jnp.float32))
        qs = lax.all_gather(q, ax)
        ss = lax.all_gather(scale, ax)
        red = jnp.sum(qs.astype(jnp.float32) * ss[:, None],
                      axis=0).astype(acc)
        dec_own = (q.astype(jnp.float32) * scale).astype(acc)
    else:
        wire_dt = _traced_wire_dtype(e, op)
        if wire_dt is not None:
            w = e.astype(wire_dt)
            red = lax.psum(w, ax).astype(acc)
            dec_own = w.astype(acc)
        else:
            red = lax.psum(e, ax)
            dec_own = e
    new_res = e - dec_own
    if op == ReduceOp.AVERAGE:
        red = red / n
    red = _scale(red, postscale)
    red_tree = jax.tree.unflatten(treedef, _unpack(red, metas))
    upd, new_inner = inner.update(red_tree, state.inner, params, **extra)
    return upd, ZeroState(new_inner, new_res[None])


# -- eager plane --------------------------------------------------------
def _eager_world():
    if basics.is_initialized() and basics.mode() == "process":
        return basics.size(), basics.rank()
    # Mesh-mode concrete / uninitialized: a single controller holds one
    # copy of everything — sharding a single process's state frees
    # nothing, so the cut is the trivial 1-way cut (documented).
    return 1, 0


# Block size (elements) of the eager ownership cut. Ownership is
# element-granular over the FLAT buffer — a leaf-granularity cut
# cannot balance a tree dominated by one big leaf (the embedding
# matrix problem) and would break the measured (n-1)/n memory claim —
# but the cut itself is still the checkpoint writer's `shard_ranges`
# balanced-by-bytes walk, applied to fixed-size blocks of the buffer.
_ZERO_BLOCK = 512


def _eager_cut(total_elems: int, itemsize: int, n: int):
    """Per-rank element ranges [lo, hi) of the flat state buffer."""
    nblocks = max((total_elems + _ZERO_BLOCK - 1) // _ZERO_BLOCK, 1)
    ranges = shard_ranges([_ZERO_BLOCK * itemsize] * nblocks, n)
    return [(min(a * _ZERO_BLOCK, total_elems),
             min(b * _ZERO_BLOCK, total_elems)) for a, b in ranges]


def _eager_zero_init(inner, params):
    leaves, _ = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("zero mode needs a non-empty params pytree")
    n, r = _eager_world()
    acc = _acc_dtype(leaves)
    total = sum(m[1] for m in _metas(leaves))
    lo, hi = _eager_cut(total, acc.itemsize, n)[r]
    seg = _pack(leaves, acc)[lo:hi]
    inner_state = inner.init(seg)
    sharded = _state_nbytes(inner_state)
    replicated = _replicated_state_bytes(inner, params)
    _set_state_gauges(sharded, replicated)
    _note_status(enabled=True, plane="eager", world=n,
                 owned_range=[lo, hi], total_elems=total,
                 sharded_state_bytes=sharded,
                 replicated_state_bytes=replicated,
                 error_feedback=False)
    return ZeroEagerState(inner_state, lo, hi, n)


def _eager_zero_update(inner, state, grads, params, op, prescale,
                       postscale, extra):
    from ..ops import allgather, grouped_allreduce

    g_leaves, treedef = jax.tree.flatten(grads)
    p_leaves = jax.tree.leaves(params)
    metas = _metas(g_leaves)
    acc = _acc_dtype(g_leaves)
    n = state.nshards
    lo, hi = state.lo, state.hi
    # Gradient reduction rides the engine's grouped path untouched —
    # native kernels, transports and wire codecs (with the engine's own
    # error feedback) all apply. The coordinator fuses these like any
    # gradient exchange; each rank then updates only its owned slice.
    red = grouped_allreduce(g_leaves, op=op, name="zero.grads",
                            prescale_factor=prescale,
                            postscale_factor=postscale)
    g_seg = _pack(red, acc)[lo:hi]
    p_seg = _pack(p_leaves, acc)[lo:hi]
    upd_seg, new_inner = inner.update(g_seg, state.inner, p_seg, **extra)
    if n == 1:
        full = upd_seg
    else:
        # Updated-segment exchange: raw full-width floats (allgatherv
        # handles the variable per-rank lengths), so every rank decodes
        # the SAME bytes — reassembly is bitwise across ranks. One
        # sentinel element pads each rank's payload so an empty owned
        # range (more ranks than blocks) still gathers.
        payload = np.concatenate(
            [np.asarray(upd_seg, dtype=acc).ravel(), np.zeros(1, acc)])
        gathered = np.asarray(allgather(payload, name="zero.updates"))
        total = sum(m[1] for m in metas)
        ranges = _eager_cut(total, acc.itemsize, n)
        parts, off = [], 0
        for a, b in ranges:
            parts.append(gathered[off:off + (b - a)])
            off += (b - a) + 1
        full = jnp.asarray(np.concatenate(parts))
    updates = jax.tree.unflatten(treedef, _unpack(full, metas))
    return updates, ZeroEagerState(new_inner, lo, hi, n)


# -- checkpoint / elasticity helpers ------------------------------------
def recut_state(state: ZeroState, params, new_world: int) -> ZeroState:
    """Re-cut a GLOBAL stacked traced ``ZeroState`` (leaves ``(n, k)``
    vectors / ``(n,)`` scalars, e.g. as materialized by
    ``JaxState.save``/``CheckpointManager``) from world size n to m.
    Content is bitwise-preserved: only the zero padding at the flat
    tail is re-sized. Shard-scalar leaves (optax counts) are identical
    across shards by construction; shard 0's value is broadcast."""
    total = sum(int(np.prod(np.shape(l), dtype=np.int64))
                for l in jax.tree.leaves(params))
    any_leaf = jax.tree.leaves(state)
    if not any_leaf:
        return state
    n = int(np.shape(any_leaf[0])[0])
    _, k = _shard_geometry(total, n)
    pad_m, k2 = _shard_geometry(total, new_world)

    def cut(l):
        a = np.asarray(l)
        if a.ndim == 1 and a.shape == (n,):
            return np.full((new_world,), a[0], a.dtype)
        if a.ndim >= 2 and a.shape[0] == n and a.shape[1] == k:
            flat = a.reshape((n * k,) + a.shape[2:])[:total]
            if pad_m:
                flat = np.concatenate(
                    [flat, np.zeros((pad_m,) + flat.shape[1:], a.dtype)])
            return flat.reshape((new_world, k2) + a.shape[2:])
        raise ValueError(
            f"unrecognized ZeroState leaf layout {a.shape} for world "
            f"{n} / shard {k} — only elementwise inner transforms "
            "(leaves (n, k) or (n,)) re-cut")

    return jax.tree.map(cut, state)


def eager_state_to_global(inner, state: ZeroEagerState, params):
    """Allgather every rank's owned flat segment into the replicated
    single-shard form (the state as if one rank owned every leaf) —
    every rank ends up holding identical trees, restoring the
    CheckpointManager's replicated-snapshot invariant so the existing
    durability plane checkpoints eager ZeRO state unchanged."""
    from ..ops import allgather

    p_leaves = jax.tree.leaves(params)
    acc = _acc_dtype(p_leaves)
    n = state.nshards
    if n == 1:
        return jax.tree.map(np.asarray, state.inner)
    total = sum(int(np.prod(np.shape(l), dtype=np.int64))
                for l in p_leaves)
    ranges = _eager_cut(total, acc.itemsize, n)
    varying = _varying_mask(inner, acc)
    leaves_s = jax.tree.leaves(state.inner)
    out = []
    for j, (leaf, var) in enumerate(zip(leaves_s, varying)):
        if not var:
            out.append(np.asarray(leaf))
            continue
        arr = np.asarray(leaf)
        payload = np.concatenate(
            [arr.ravel(), np.zeros(1, arr.dtype)])
        gathered = np.asarray(allgather(payload, name=f"zero.state.{j}"))
        parts, off = [], 0
        for a, b in ranges:
            parts.append(gathered[off:off + (b - a)])
            off += (b - a) + 1
        out.append(np.concatenate(parts))
    return jax.tree.unflatten(jax.tree.structure(state.inner), out)


def eager_state_from_global(inner, global_inner, params,
                            world: Optional[int] = None,
                            rank: Optional[int] = None) -> ZeroEagerState:
    """Re-cut a replicated single-shard inner state (from
    `eager_state_to_global`, a checkpoint restore, or a world-size
    change) to this rank's owned segment — the n→m restore path.
    Bitwise: the flat per-element arrays are sliced verbatim."""
    if world is None or rank is None:
        world, rank = _eager_world()
    p_leaves = jax.tree.leaves(params)
    acc = _acc_dtype(p_leaves)
    total = sum(int(np.prod(np.shape(l), dtype=np.int64))
                for l in p_leaves)
    lo, hi = _eager_cut(total, acc.itemsize, world)[rank]
    varying = _varying_mask(inner, acc)
    out = [np.asarray(l)[lo:hi] if var else np.asarray(l)
           for l, var in zip(jax.tree.leaves(global_inner), varying)]
    return ZeroEagerState(
        jax.tree.unflatten(jax.tree.structure(global_inner), out),
        lo, hi, world)


def _varying_mask(inner, acc):
    """Which inner-state leaves scale with the flat segment length
    (cut-able moments) vs shared scalars (optax counts) — probed
    abstractly by comparing init structures at two segment lengths."""
    a = jax.tree.leaves(jax.eval_shape(inner.init,
                                       jax.ShapeDtypeStruct((1,), acc)))
    b = jax.tree.leaves(jax.eval_shape(inner.init,
                                       jax.ShapeDtypeStruct((2,), acc)))
    return [x.shape != y.shape for x, y in zip(a, b)]


# -- ergonomics ---------------------------------------------------------
def state_specs(axis_name: str, zero: bool = True):
    """The shard_map in/out PartitionSpec prefix for a
    DistributedOptimizer state under jit: with ZeRO every leaf carries
    the leading shard dim, so one uniform ``P(axis)`` shards the whole
    tree; EF-only states shard just the residual."""
    from jax.sharding import PartitionSpec as P

    if zero:
        return P(axis_name)
    return ZeroState(inner=P(), residual=P(axis_name))


def _pick_mesh_axis(mesh, axis_name: Optional[str]) -> str:
    """Mirror of `hvd.resolve_axis` for a concrete mesh: explicit wins,
    then the init axis, then the canonical data axes, then the first
    mesh axis (1-D meshes)."""
    names = tuple(mesh.axis_names)
    if axis_name is not None:
        return axis_name
    an = basics.axis_name() if basics.is_initialized() else None
    for cand in ((an,) if an else ()) + ("dp", "hvd"):
        if cand in names:
            return cand
    return names[0]


def zero_init(tx, params, mesh, axis_name: Optional[str] = None):
    """Initialize a ZeRO/EF-wrapped `DistributedOptimizer` state as a
    GLOBAL sharded array over `mesh` — the out-of-jit spelling of
    "init runs inside shard_map" (traced init needs the axis size,
    which a plain `tx.init(params)` outside a trace cannot know). `tx`
    is the WRAPPED transformation (`DistributedOptimizer(inner,
    zero=...)`). Returns stacked leaves ((n, ...) global) sharded over
    the data axis; pass them into the training step with in_specs
    `hvd.zero_state_specs(axis)`."""
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import set_mesh, shard_map

    ax = _pick_mesh_axis(mesh, axis_name)
    f = shard_map(lambda p: tx.init(p), mesh=mesh, in_specs=(P(),),
                  out_specs=state_specs(ax, zero=True))
    with set_mesh(mesh):
        state = jax.jit(f)(params)
    n = int(np.prod([mesh.shape[a] for a in
                     (ax if isinstance(ax, tuple) else (ax,))]))
    # Measured from the actual state: the global stacked tree is what a
    # full replica would hold per rank (modulo the flat-tail padding);
    # each device keeps a 1/n share — the number that drops (n-1)/n.
    replicated = _state_nbytes(state)
    sharded = replicated // max(n, 1)
    _set_state_gauges(sharded, replicated)
    _note_status(enabled=True, plane="traced", world=n, axis=ax,
                 sharded_state_bytes=sharded,
                 replicated_state_bytes=replicated)
    return state


# -- the optax wrapper (called by DistributedOptimizer) -----------------
def zero_optimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: Optional[str] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    stage: int = 1,
    error_feedback: bool = False,
) -> optax.GradientTransformationExtraArgs:
    """The ZeRO/EF gradient transformation behind
    `DistributedOptimizer(zero=..., error_feedback=...)`. `stage` 0
    means EF-only (replicated state, residual-corrected wire cast)."""
    if stage not in (0, 1, 2):
        raise ValueError(f"zero stage must be 0/1/2, got {stage!r}")
    if stage == 0 and not error_feedback:
        raise ValueError("zero_optimizer needs stage>=1 or error_feedback")

    def _resolved_axis():
        from ..ops import resolve_axis

        ax = resolve_axis(axis_name)
        if ax is None and basics.is_initialized():
            an = basics.axis_name()
            from ..ops import _bound_axes

            ax = an if an in _bound_axes() else None
        return ax

    def init_fn(params):
        leaves = jax.tree.leaves(params)
        if leaves and _is_tracer(leaves[0]):
            ax = _resolved_axis()
            if ax is None:
                raise ValueError(
                    "traced ZeRO init needs a bound data axis — init "
                    "inside shard_map over the mesh, or use "
                    "hvd.optim.zero_init(tx, params, mesh)")
            if stage:
                st = _traced_zero_init(optimizer, leaves, ax,
                                       error_feedback)
            else:
                st = _traced_ef_init(optimizer, leaves, params, ax)
            _note_status(enabled=True, plane="traced",
                         stage=stage, error_feedback=error_feedback)
            return st
        if stage:
            st = _eager_zero_init(optimizer, params)
            with _status_lock:
                _status["stage"] = stage
            return st
        # EF-only, concrete: the residual corrects the TRACED wire
        # cast; eagerly it stays zeros (the engine codec carries its
        # own residual store) but the state shape matches the traced
        # plane so one checkpoint format serves both.
        total = sum(int(np.prod(np.shape(l), dtype=np.int64))
                    for l in leaves)
        return ZeroState(optimizer.init(params),
                         jnp.zeros((1, total), _acc_dtype(leaves)))

    def update_fn(grads, state, params=None, **extra):
        from .distributed import _mark_traced_step
        from ..common import goodput

        if params is None:
            raise ValueError(
                "DistributedOptimizer(zero=...) updates need params= "
                "(the owned shard is sliced from them)")
        led = goodput.active()
        leaves = jax.tree.leaves(grads)
        # Constant gradients under jit (e.g. a closed-over pytree) are
        # not tracers, but the params always are — either means we are
        # inside a trace and must lower to the collective ops.
        traced = any(_is_tracer(l)
                     for l in leaves + jax.tree.leaves(params))
        if led is not None and led.enabled:
            if traced:
                _mark_traced_step()
            else:
                led.auto_step("optim")
        if traced:
            ax = _resolved_axis()
            if ax is None:
                raise ValueError(
                    "traced ZeRO update needs a bound data axis; wrap "
                    "the step in shard_map over the data axis")
            if stage:
                return _traced_zero_update(
                    optimizer, state, grads, params, ax, op,
                    prescale_factor, postscale_factor, error_feedback,
                    extra)
            return _traced_ef_update(
                optimizer, state, grads, params, ax, op,
                prescale_factor, postscale_factor, extra)
        if stage:
            return _eager_zero_update(
                optimizer, state, grads, params, op, prescale_factor,
                postscale_factor, extra)
        # EF-only, concrete: plain engine reduction + inner update.
        from .distributed import _allreduce_grads

        red = _allreduce_grads(grads, op, axis_name, prescale_factor,
                               postscale_factor, None, False)
        upd, new_inner = optimizer.update(red, state.inner, params,
                                          **extra)
        return upd, ZeroState(new_inner, state.residual)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)
