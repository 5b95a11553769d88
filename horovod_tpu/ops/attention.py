"""The one door to attention: a caller describes the mask and hands over
heads as they are; the kernel and the mesh axes to manualize are chosen
here, from shapes and the ambient mesh.

    attention(q, k, v, *, causal, window=None, mask=None, impl="dense")

q (B, S, H, Dqk), k (B, S, H_kv, Dqk), v (B, S, H_kv, Dv) -> (B, S, H,
Dv) in q's dtype; scores are scaled by 1/sqrt(Dqk).

* The mask is a description, never a tensor of scores: `causal` (key j
  visible to query i iff j <= i), `window` (and i - j < window; causal
  only), `mask` ((B, S) key validity, 1 = attend; fully masked rows
  give zeros).
* The head layout is read off the shapes: `H_kv` divides `H`, query
  head h attends key/value head `h // (H / H_kv)`; `Dqk` may differ
  from `Dv` (a latent attention's 192 beside 128).
* `impl`: "dense" (XLA, scores materialised), "flash" (the Pallas
  kernels of ops/flash_attention.py, which skip the key tiles a causal
  or windowed query tile cannot see), or the sequence-parallel "ring" /
  "ulysses" over `sp_axis` (dense where the ambient mesh has no such
  axis; they take neither a window nor grouped heads).

A `pallas_call` is opaque to GSPMD, which would replicate it on every
chip; so under a mesh whose `dp` / `tp` axes are larger than one the
flash call runs in a `shard_map` that manualizes them: attention is
independent per (batch, head), no collective is needed inside.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..utils.compat import get_abstract_mesh, shard_map
from .flash_attention import flash_attention

IMPLS = ("dense", "flash", "ring", "ulysses")


def _large_axes(mesh, *names) -> list:
    return [ax for ax in names if mesh is not None
            and ax in mesh.axis_names and mesh.shape[ax] > 1]


def dense_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    mask=None):
    """XLA attention with the (B, H, S, S) scores materialised; softmax
    in float32. Grouped key/value heads are repeated to the query
    heads'."""
    H, S = q.shape[2], q.shape[1]
    if k.shape[2] != H:
        k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = scores.astype(jnp.float32)
    valid = None
    if causal:
        valid = jnp.tril(jnp.ones((S, S), dtype=bool))[None, None]
    if window is not None:
        near = jnp.triu(jnp.ones((S, S), dtype=bool), 1 - window)
        valid = jnp.logical_and(valid, near[None, None])
    if mask is not None:
        # mask: (B, S) 1 = attend, 0 = pad.
        km = mask[:, None, None, :].astype(bool)
        valid = km if valid is None else jnp.logical_and(valid, km)
    if valid is not None:
        scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if valid is not None:
        # Fully-masked query rows yield zeros, not a uniform average of
        # every value — matching the sp kernels' convention
        # (parallel/ring.py _flash_block_update).
        probs = jnp.where(valid, probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _flash(q, k, v, causal, window, mask, mesh):
    manual = _large_axes(mesh, "dp", "tp")
    kernel = functools.partial(flash_attention, causal=causal, window=window)
    args = (q, k, v) if mask is None else (q, k, v, mask)
    if not manual:
        return kernel(*args).astype(q.dtype)
    dp = "dp" if "dp" in manual else None
    tp = "tp" if "tp" in manual else None
    if tp and k.shape[2] % mesh.shape[tp]:
        raise ValueError(
            f"{k.shape[2]} key/value heads do not divide over tp="
            f"{mesh.shape[tp]}")
    qkv_spec = P(dp, None, tp, None)   # (B, S, H, D)
    specs = (qkv_spec,) * 3 + ((P(dp, None),) if mask is not None else ())
    fn = shard_map(lambda *a: kernel(*a), mesh=mesh, in_specs=specs,
                   out_specs=qkv_spec, axis_names=set(manual))
    return fn(*args).astype(q.dtype)


def _sequence_parallel(q, k, v, causal, mask, impl, mesh, sp_axis,
                       sp_use_flash):
    """The sp kernels in a nested shard_map that manualizes `sp_axis`;
    batch and head sharding stays under GSPMD unless the flash kernel
    runs inside (then dp / tp too, for the reason in the module
    docstring). The padding mask rides sequence-sharded like K / V: ring
    rotates it, Ulysses all-gathers it."""
    from ..parallel.ring import ring_attention
    from ..parallel.ulysses import ulysses_attention

    if impl == "ring":
        kernel = ring_attention
    else:
        kernel = functools.partial(ulysses_attention, use_flash=sp_use_flash)
    manual = {sp_axis}
    dp = tp = None
    if impl != "ring" and sp_use_flash:
        dp, tp = (ax if _large_axes(mesh, ax) else None
                  for ax in ("dp", "tp"))
        manual |= {ax for ax in (dp, tp) if ax}
    spec = P(dp, sp_axis, tp)       # (B, S, H, D)
    if mask is None:
        fn = shard_map(
            lambda q, k, v: kernel(q, k, v, sp_axis, causal=causal),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            axis_names=manual)
        return fn(q, k, v)
    fn = shard_map(
        lambda q, k, v, m: kernel(q, k, v, sp_axis, causal=causal, mask=m),
        mesh=mesh, in_specs=(spec,) * 3 + (P(dp, sp_axis),), out_specs=spec,
        axis_names=manual)
    return fn(q, k, v, mask)


def attention(q, k, v, *, causal: bool, window: Optional[int] = None,
              mask=None, impl: str = "dense", sp_axis: str = "sp",
              sp_use_flash: bool = False):
    """Attention as the module docstring describes it."""
    if impl not in IMPLS:
        raise ValueError(f"impl {impl!r}: one of {IMPLS}")
    H, H_kv = q.shape[2], k.shape[2]
    if H % H_kv or v.shape[2] != H_kv:
        raise ValueError(f"{H} query heads over {H_kv} key and "
                         f"{v.shape[2]} value heads")
    if window is not None and not causal:
        raise ValueError("a window is a causal window")
    if impl == "flash":
        return _flash(q, k, v, causal, window, mask, get_abstract_mesh())
    if impl == "dense" or not _large_axes(mesh := get_abstract_mesh(),
                                          sp_axis):
        return dense_attention(q, k, v, causal=causal, window=window,
                               mask=mask)
    if window is not None or H_kv != H:
        raise NotImplementedError(
            f"impl {impl!r} takes neither a window nor grouped heads")
    return _sequence_parallel(q, k, v, causal, mask, impl, mesh, sp_axis,
                              sp_use_flash)
