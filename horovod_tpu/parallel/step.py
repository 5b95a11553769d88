"""wrap_step: run a training-step function SPMD over the mesh.

This is the TPU-native answer to "wrap your optimizer and your script
scales" (ref: README.rst:80-99): the user writes a single-chip step
function that calls hvd.allreduce (or uses hvd.DistributedOptimizer);
`wrap_step` shard_maps it over the data axis so each chip sees its batch
shard, hvd collectives bind to the mesh axis, and XLA compiles one SPMD
program with ICI collectives — no background thread, no negotiation.
"""
from __future__ import annotations

import functools
import itertools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common import basics, goodput, tracing
from ..utils.compat import shard_map

# The `_Step` whose call is in flight on this thread, tracing included
# (the first call of a built step traces the user's function here), and
# None outside one. jax keys a trace by this value, so a function traced
# under a step's call is traced again when called outside one.
_step_in_flight = jax.make_user_context(None)


class _Step:
    """One built step of a `wrap_step` function. `holds_update` is set
    by the optimizer update traced under its call: each call is then one
    training step, marked on the goodput ledger from the host."""

    __slots__ = ("call", "holds_update")

    def __init__(self, call):
        self.call = call
        self.holds_update = False


def claim_step_boundary() -> bool:
    """For a traced optimizer update: whether a `wrap_step` call is in
    flight around it. That call then marks the step on the goodput
    ledger (docs/goodput.md) and the update stages no marker of its own
    into the compiled program."""
    step = _step_in_flight.value
    if step is None:
        return False
    step.holds_update = True
    return True


def wrap_step(
    fn: Callable = None,
    *,
    mesh=None,
    axis_name: Optional[str] = None,
    sharded_argnums: Optional[Sequence[int]] = None,
    replicated_argnums: Sequence[int] = (0,),
    out_replicated: bool = True,
    jit: bool = True,
    donate_argnums: Tuple[int, ...] = (),
):
    """Decorate a step function for SPMD execution.

    By default argument 0 (params / train state) is replicated and every
    other argument is sharded along its leading (batch) dim; the output
    is replicated (gradients inside should already be allreduced via
    hvd.allreduce / DistributedOptimizer — shard_map will verify
    replication only where cheap).

    Usage:
        @hvd.wrap_step
        def train_step(state, batch): ...
    """
    if fn is None:
        return functools.partial(
            wrap_step,
            mesh=mesh,
            axis_name=axis_name,
            sharded_argnums=sharded_argnums,
            replicated_argnums=replicated_argnums,
            out_replicated=out_replicated,
            jit=jit,
            donate_argnums=donate_argnums,
        )

    # Compiled-step cache: jax.jit caches on function identity, so the
    # shard_map/jit construction must happen once per (mesh, arg
    # structure/shape/dtype) signature, not per call — otherwise every
    # training step would re-trace.
    cache = {}

    def build(m, an, args):
        repl = set(replicated_argnums)
        if sharded_argnums is not None:
            shard = set(sharded_argnums)
            repl = set(range(len(args))) - shard
        in_specs = tuple(
            jax.tree.map(lambda _: P() if i in repl else P(an), args[i])
            for i in range(len(args))
        )
        out_spec = P() if out_replicated else P(an)

        # Mark replicated inputs as axis-varying inside the body.
        # Without this, jax's manual-axes tracking auto-psums the
        # cotangent of any replicated input, so a user's jax.grad
        # inside the step already returns the cross-rank SUM and a
        # subsequent hvd.allreduce(AVERAGE) cannot recover the
        # per-rank average (it sees identical values on every
        # shard). pvary keeps grads rank-local — the reference's
        # semantics, where each rank owns its gradient until the
        # explicit allreduce (ref: horovod/torch/optimizer.py:114-149).
        def local_fn(*inner):
            from ..utils.compat import pvary

            inner = tuple(
                jax.tree.map(lambda x: pvary(x, an), a)
                if i in repl else a
                for i, a in enumerate(inner)
            )
            return fn(*inner)

        # out_specs is a prefix pytree: one spec covers the whole
        # output tree (eval_shape-ing fn here would trace its
        # collectives outside the mesh and hit unbound axis names).
        sm = shard_map(
            local_fn, mesh=m,
            in_specs=in_specs,
            out_specs=out_spec,
        )
        if jit:
            sm = jax.jit(sm, donate_argnums=donate_argnums)
        return sm

    # Each call is one step span of the XLA profile, numbered from 0,
    # with the wrapper's own parts as its children (docs/tracing.md
    # "Under jit"), and one step of the goodput ledger where the step
    # holds an optimizer update.
    calls = itertools.count()

    @functools.wraps(fn)
    def wrapped(*args):
        with tracing.annotate(tracing.SPAN_STEP, step=next(calls)):
            m = mesh if mesh is not None else basics.mesh()
            an = axis_name if axis_name is not None else basics.axis_name()
            if m is None:
                raise RuntimeError(
                    "wrap_step requires mesh mode (hvd.init())")
            with tracing.annotate(tracing.SPAN_WRAP_PREPARE):
                leaves, treedef = jax.tree.flatten(args)
                key = (
                    id(m), treedef,
                    tuple((getattr(l, "shape", ()),
                           str(getattr(l, "dtype", type(l))))
                          for l in leaves),
                )
                step = cache.get(key)
                # Traced by an outer jax.jit this call runs once, not
                # once per executed step: it is no boundary of a step,
                # and the update's staged marker stands.
                on_host = not any(
                    isinstance(l, jax.core.Tracer) for l in leaves)
            if step is None:
                with tracing.annotate(tracing.SPAN_WRAP_BUILD):
                    step = cache[key] = _Step(build(m, an, args))
            with tracing.annotate(tracing.SPAN_WRAP_CALL):
                if not on_host:
                    return step.call(*args)
                with _step_in_flight(step):
                    out = step.call(*args)
            if step.holds_update:
                goodput.auto_step("wrap_step")
            return out

    return wrapped
