"""The one place the framework spells jax's mesh / shard_map surface.

Written for the jax this repository runs on (0.9). No fallbacks for
other versions live here; the wrappers exist so call sites share one
spelling of partial manualization and of the VMA annotation.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=False,
              axis_names=None):
    kw = {}
    if axis_names is not None:
        # Partial manualization: only these axes become manual;
        # the rest stay under GSPMD inside the body.
        kw["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep, **kw)


def set_mesh(mesh):
    """Ambient-mesh context: nested shard_maps inside a model resolve
    their axes against it at trace time."""
    return jax.sharding.set_mesh(mesh)


def get_abstract_mesh():
    return jax.sharding.get_abstract_mesh()


def axis_size(axis_name):
    """Static size of a named mesh axis from inside shard_map."""
    return jax.lax.axis_size(axis_name)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def pvary(x, axis):
    """Mark x as varying over `axis` for shard_map's VMA tracking.
    No-op under check_vma=False (our shard_map default); under VMA
    tracking it keeps jax.grad cotangents rank-local instead of
    auto-psummed, preserving Horovod's per-rank-gradient semantics."""
    return jax.lax.pcast(x, axis, to="varying")
