"""Under `hvd.wrap_step` the goodput ledger's step is marked by the host
call (docs/goodput.md "Step demarcation"): the compiled step holds no
host callback, N calls count N steps with the `wrap_step` source
driving, an explicit `hvd.step()` scope still wins, and a call traced
by an outer `jax.jit` keeps the optimizer's staged marker."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import goodput
from horovod_tpu.common.telemetry import MetricsRegistry
from horovod_tpu.optim.zero import zero_init

BY_SOURCE = "horovod_goodput_steps_by_source_total"


@pytest.fixture
def ledger():
    """A fresh enabled ledger on a registry of its own as the process's."""
    led = goodput.GoodputLedger(registry=MetricsRegistry(), rank=0,
                                enabled=True, stamp_path=None)
    prev = goodput.active()
    goodput.set_current(led)
    yield led
    goodput.set_current(prev)


@pytest.fixture(params=[1, 8], ids=["1dev", "8dev"])
def world(request):
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:request.param])
    yield request.param
    hvd.shutdown()


def by_source(led):
    """{source: steps} of the ledger's per-source counters."""
    prefix = BY_SOURCE + '{source="'
    return {key[len(prefix):-2]: value
            for key, value in led.registry.snapshot().items()
            if key.startswith(prefix)}


def replicated_step(n):
    """(wrapped step, its arguments): sgd on a vector through
    `hvd.DistributedOptimizer`, the state replicated."""
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.zeros(8, jnp.float32)

    def step(w, s, x):
        g = jax.grad(lambda wv: jnp.sum(wv * x))(w)
        upd, s2 = tx.update(g, s, w)
        return optax.apply_updates(w, upd), s2

    x = jnp.arange(8.0 * n, dtype=jnp.float32)
    return (hvd.wrap_step(step, replicated_argnums=(0, 1)),
            (w, tx.init(w), x), tx)


def zero_step(n):
    """The same with the optimizer's state sharded (`zero=1`): state in
    and out carry the shard dimension, so every output is per shard."""
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=1)
    w = jnp.zeros(8, jnp.float32)
    state = zero_init(tx, w, hvd.mesh())

    def step(w, s, x):
        g = jax.grad(lambda wv: jnp.sum(wv * x))(w)
        upd, s2 = tx.update(g, s, w)
        return s2, optax.apply_updates(w, upd)[None]

    x = jnp.arange(8.0 * n, dtype=jnp.float32)
    return (hvd.wrap_step(step, sharded_argnums=(1, 2),
                          out_replicated=False),
            (w, state, x), tx)


STEPS = {"replicated": replicated_step, "zero1": zero_step}


@pytest.fixture
def traced_jits(monkeypatch):
    """Every `jax.jit` built while this is in use also records the
    trace of each call, made where the call is made (so under whatever
    `wrap_step` has in flight): [jax.stages.Traced, ...]."""
    record = []
    jit = jax.jit

    def recording_jit(f, **kw):
        jitted = jit(f, **kw)

        def call(*args):
            record.append(jitted.trace(*args))
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", recording_jit)
    return record


def holds_callback(traced):
    """(in the jaxpr, in the lowered text) of one recorded trace."""
    return ("debug_callback" in str(traced.jaxpr),
            "callback" in traced.lower().as_text().lower())


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_compiled_step_holds_no_callback(world, ledger, traced_jits, kind):
    wrapped, args, _ = STEPS[kind](world)
    del traced_jits[:]  # what building the arguments jitted
    jax.block_until_ready(wrapped(*args))
    (traced,) = traced_jits
    assert holds_callback(traced) == (False, False)
    # The update is in there all the same, and was counted from the host.
    assert "mul" in str(traced.jaxpr)
    assert ledger.steps == 1
    assert by_source(ledger) == {"wrap_step": 1}


def test_bare_jit_step_still_holds_the_callback(hvd_mesh, ledger,
                                                traced_jits):
    """What the check above looks for is there where it should be."""
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.zeros(8, jnp.float32)

    def step(w, s, g):
        upd, s2 = tx.update(g, s, w)
        return optax.apply_updates(w, upd), s2

    jax.block_until_ready(jax.jit(step)(w, tx.init(w), jnp.ones(8)))
    jax.effects_barrier()
    (traced,) = traced_jits
    assert holds_callback(traced) == (True, True)
    assert by_source(ledger) == {"optim": 1}


def eager_update(tx, w):
    tx.update(jnp.ones_like(w), tx.init(w), w)
    return 1


def bare_jit_steps(tx, w):
    @jax.jit
    def step(w, s, g):
        upd, s2 = tx.update(g, s, w)
        return optax.apply_updates(w, upd), s2

    s = tx.init(w)
    for _ in range(3):
        w, s = step(w, s, jnp.ones_like(w))
    jax.block_until_ready(w)
    jax.effects_barrier()
    return 3


BEFORE = {"nothing": lambda tx, w: 0, "eager_update": eager_update,
          "bare_jit": bare_jit_steps}


@pytest.mark.parametrize("kind,before", [
    ("replicated", "nothing"), ("replicated", "eager_update"),
    ("replicated", "bare_jit"), ("zero1", "nothing")])
def test_n_calls_count_n_steps_with_wrap_step_driving(world, ledger, kind,
                                                      before):
    """Also after the optimizer's own marker drove the counter in this
    process: the higher-ranked source takes over, nothing counts twice."""
    wrapped, args, tx = STEPS[kind](world)
    earlier = BEFORE[before](tx, args[0])
    assert ledger.steps == earlier
    timed_earlier = ledger.timed_steps
    n = 5
    for _ in range(n):
        out = wrapped(*args)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert ledger.steps == earlier + n
    expected = {"wrap_step": n}
    if earlier:
        expected["optim"] = earlier
    assert by_source(ledger) == expected
    assert ledger.registry.snapshot()[
        "horovod_goodput_steps_total"] == earlier + n
    # Steps between two calls are timed; the first has no start.
    assert ledger.timed_steps - timed_earlier == n - 1


def test_optimizer_marker_is_ignored_once_wrap_step_drove(hvd_mesh, ledger):
    wrapped, args, tx = replicated_step(8)
    jax.block_until_ready(wrapped(*args))
    assert eager_update(tx, args[0]) == 1
    assert bare_jit_steps(tx, args[0]) == 3
    assert ledger.steps == 1 and by_source(ledger) == {"wrap_step": 1}


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_explicit_scope_still_drives(world, ledger, kind):
    wrapped, args, _ = STEPS[kind](world)
    n = 4
    for _ in range(n):
        with hvd.step():
            out = wrapped(*args)
    jax.block_until_ready(out)
    assert ledger.steps == n
    assert by_source(ledger) == {"explicit": n}
    # ... also when the wrapper drove before the scope came.
    jax.block_until_ready(wrapped(*args))
    assert ledger.steps == n


def test_explicit_scope_takes_over_from_wrap_step(hvd_mesh, ledger):
    wrapped, args, _ = replicated_step(8)
    for _ in range(2):
        out = wrapped(*args)
    with hvd.step():
        out = wrapped(*args)
    jax.block_until_ready(out)
    assert by_source(ledger) == {"wrap_step": 2, "explicit": 1}
    assert ledger.steps == 3


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_call_traced_by_outer_jit_keeps_staged_marker(world, ledger,
                                                      traced_jits, kind):
    """No host call per executed step exists there: the optimizer's
    marker is staged as before and counts the executed steps."""
    wrapped, args, _ = STEPS[kind](world)
    del traced_jits[:]  # what building the arguments jitted
    outer = jax.jit(lambda *a: wrapped(*a))
    n = 4
    for _ in range(n):
        out = outer(*args)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert holds_callback(traced_jits[0]) == (True, True)
    assert ledger.steps == n
    assert by_source(ledger) == {"optim": n}
    # The same wrapped function called from the host afterwards is
    # traced again, without the marker, and takes the counter over.
    del traced_jits[:]
    jax.block_until_ready(wrapped(*args))
    assert holds_callback(traced_jits[-1]) == (False, False)
    assert by_source(ledger) == {"optim": n, "wrap_step": 1}


def test_step_without_an_update_counts_nothing(hvd_mesh, ledger):
    """An evaluation step in `wrap_step` is no training step."""
    @hvd.wrap_step
    def evaluate(w, x):
        return hvd.allreduce(jnp.sum(w * x))

    for _ in range(3):
        out = evaluate(jnp.ones(8), jnp.arange(64.0))
    jax.block_until_ready(out)
    assert ledger.steps == 0 and by_source(ledger) == {}


def test_inner_jit_is_traced_for_each_side(hvd_mesh, ledger):
    """A jitted update shared by a raw `shard_map` loop and a
    `wrap_step` step: jax keys its trace by the call in flight, so the
    one holds the staged marker and the other does not."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.utils.compat import shard_map

    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.zeros(8, jnp.float32)

    @jax.jit
    def update(w, s, g):
        upd, s2 = tx.update(g, s, w)
        return optax.apply_updates(w, upd), s2

    def step(w, s, x):
        return update(w, s, jax.grad(lambda wv: jnp.sum(wv * x))(w))

    x = jnp.arange(64.0, dtype=jnp.float32)
    raw = jax.jit(shard_map(step, mesh=hvd.mesh(),
                            in_specs=(P(), P(), P("hvd")),
                            out_specs=(P(), P())))
    wrapped = hvd.wrap_step(step, replicated_argnums=(0, 1))
    for fn, source in ((raw, "optim"), (wrapped, "wrap_step")):
        for _ in range(2):
            out = fn(w, tx.init(w), x)
        jax.block_until_ready(out)
        jax.effects_barrier()
        assert by_source(ledger).get(source) == 2, by_source(ledger)
    assert ledger.steps == 4


def test_disabled_ledger_marks_nothing(hvd_mesh, ledger):
    ledger.enabled = False
    wrapped, args, _ = replicated_step(8)
    for _ in range(3):
        out = wrapped(*args)
    jax.block_until_ready(out)
    assert ledger.steps == 0


def test_failed_call_counts_no_step(hvd_mesh, ledger):
    wrapped, args, _ = replicated_step(8)
    jax.block_until_ready(wrapped(*args))
    with pytest.raises(Exception):
        wrapped(args[0], args[1], jnp.arange(7.0))  # 7 rows over 8 shards
    assert ledger.steps == 1


def test_results_are_the_staged_marker_step_s(hvd_mesh, ledger):
    """The mark left the program, the mathematics did not: the step
    under `wrap_step` gives what the same step gives under a raw
    `shard_map` with the staged marker."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.utils.compat import shard_map

    tx = hvd.DistributedOptimizer(optax.adam(1e-2))
    w = jnp.linspace(-1.0, 1.0, 8, dtype=jnp.float32)

    def step(w, s, x):
        g = jax.grad(lambda wv: jnp.sum(jnp.tanh(wv * x[:8])))(w)
        upd, s2 = tx.update(g, s, w)
        return optax.apply_updates(w, upd), s2

    x = jnp.arange(64.0, dtype=jnp.float32) / 64.0
    raw = jax.jit(shard_map(step, mesh=hvd.mesh(),
                            in_specs=(P(), P(), P("hvd")),
                            out_specs=(P(), P())))
    wrapped = hvd.wrap_step(step, replicated_argnums=(0, 1))
    got, want = (w, tx.init(w)), (w, tx.init(w))
    for _ in range(3):
        got = wrapped(*got, x)
        want = raw(*want, x)
    jax.effects_barrier()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
