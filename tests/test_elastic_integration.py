"""Elastic end-to-end tests with REAL worker processes and a scripted
discovery whose output changes mid-training (ref test model:
test/integration/elastic_common.py — hosts added, fault tolerance via
injected worker death)."""
import os
import pickle
import subprocess
import sys
import textwrap
import time

import pytest

from horovod_tpu.runner.elastic.discovery import HostDiscoveryScript
from horovod_tpu.runner.elastic.driver import ElasticDriver
from horovod_tpu.runner.launch import slot_env, spawn_worker
from horovod_tpu.runner.rendezvous_server import RendezvousServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent(
    """
    import os, pickle, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.backend.elastic_env import spawn_identity
    from horovod_tpu.backend.rendezvous import RendezvousClient
    from horovod_tpu.elastic.state import ObjectState
    from horovod_tpu.utils import env as env_cfg

    TOTAL = int(os.environ["TEST_TOTAL_BATCHES"])
    FAIL_KEY = os.environ.get("TEST_FAIL_KEY")
    FAIL_SENTINEL = os.environ.get("TEST_FAIL_SENTINEL")

    hvd.init()
    state = ObjectState(batch=0, history=[])

    @hvd.elastic.run
    def train(state):
        while state.batch < TOTAL:
            if (
                FAIL_KEY
                and spawn_identity() == FAIL_KEY
                and not os.path.exists(FAIL_SENTINEL)
                and state.batch >= 3
            ):
                open(FAIL_SENTINEL, "w").close()
                os._exit(1)
            hvd.allreduce(np.ones(2, np.float32), name="g")
            state.history.append((hvd.rank(), hvd.size()))
            state.batch += 1
            state.commit()
            time.sleep(0.05)
        return list(state.history)

    hist = train(state)
    rdv = RendezvousClient(
        env_cfg.get_str(env_cfg.RENDEZVOUS_ADDR),
        env_cfg.get_int(env_cfg.RENDEZVOUS_PORT, 0),
    )
    rdv.put("test_results", spawn_identity(), pickle.dumps((hvd.rank(), hist)))
    print(f"worker {spawn_identity()} done as rank {hvd.rank()}")
    """
)


_GSPMD_WORKER = textwrap.dedent(
    """
    import os, pickle, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.backend.elastic_env import spawn_identity
    from horovod_tpu.backend.rendezvous import RendezvousClient
    from horovod_tpu.elastic.state import JaxState
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.utils import env as env_cfg

    TOTAL = int(os.environ["TEST_TOTAL_BATCHES"])

    hvd.init()
    TRACES = {"n": 0}

    def build_step():
        # Mesh REBUILD on every (re)entry: a fresh 2-device local mesh
        # and a fresh wrap_step jit. The closure reads hvd.size(), so a
        # topology change makes the retraced computation genuinely
        # different (world-size scaling baked into the trace).
        mesh = create_mesh({"dp": 2}, devices=jax.devices()[:2])
        world = hvd.size()

        def step(w, x, y):
            TRACES["n"] += 1  # python body runs once per TRACE
            def loss_fn(w):
                return ((x @ w - y) ** 2).mean()

            loss, g = jax.value_and_grad(loss_fn)(w)
            # Local-mesh combine on the TRACED plane (XLA psum over the
            # dp axis inside shard_map), pre-scaled for the world
            # average that the engine completes across processes.
            g = hvd.allreduce(g, axis_name="dp") / world
            loss = hvd.allreduce(loss, axis_name="dp")
            return g, loss

        return hvd.wrap_step(step, mesh=mesh, replicated_argnums=(0,))

    state = JaxState(params=np.zeros((4,), np.float32), batch=0,
                     history=[])

    X = np.arange(32.0, dtype=np.float32).reshape(8, 4) / 32.0
    W_TRUE = np.array([1.0, 2.0, -1.0, 0.5], np.float32)
    Y = X @ W_TRUE

    @hvd.elastic.run
    def train(state):
        step = build_step()  # mesh rebuild + retrace after every reset
        while state.batch < TOTAL:
            g_local, loss = step(state.params, X, Y)
            # Cross-worker combine rides the engine (process plane);
            # the traced step already divided by world size.
            g = hvd.allreduce(np.asarray(g_local), name="g",
                              average=False)
            state.params = state.params - 0.5 * np.asarray(g)
            state.history.append(
                (hvd.rank(), hvd.size(), TRACES["n"])
            )
            state.batch += 1
            state.commit()
            time.sleep(0.03)
        return list(state.history), np.asarray(state.params)

    hist, params = train(state)
    rdv = RendezvousClient(
        env_cfg.get_str(env_cfg.RENDEZVOUS_ADDR),
        env_cfg.get_int(env_cfg.RENDEZVOUS_PORT, 0),
    )
    rdv.put("test_results", spawn_identity(),
            pickle.dumps((hvd.rank(), (hist, params.tolist()))))
    print(f"worker {spawn_identity()} done as rank {hvd.rank()}")
    """
)


def _run_elastic(tmp_path, discovery_script, min_np, max_np, worker_env,
                 timeout=180, on_worker_meshed=None, worker_src=_WORKER):
    """on_worker_meshed: optional callback fired (from a watcher thread)
    once the first worker has registered its notification endpoint —
    i.e. it is initialized and entering the training loop (a size-1
    worker builds no TCP mesh, so the notify registration is the
    reliable liveness signal). Event-driven replacement for fixed
    sleeps when a test needs to change topology mid-run."""
    os.environ["HVDRUN_FORCE_LOCAL"] = "1"
    server = RendezvousServer()
    port = server.start()

    if on_worker_meshed is not None:
        import threading

        def _watch():
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if server.handle_get("workers_notify/hostA:0") is not None:
                    on_worker_meshed()
                    return
                time.sleep(0.05)

        threading.Thread(target=_watch, daemon=True).start()
    driver = ElasticDriver(
        server, HostDiscoveryScript(discovery_script, 1), min_np, max_np,
        poll_interval=0.25,
    )

    script = tmp_path / "worker.py"
    script.write_text(worker_src)

    def create_worker(slot, extra_env):
        env = slot_env(slot, "127.0.0.1", port, dict(worker_env),
                       elastic=True)
        env.update(extra_env)
        env["PYTHONPATH"] = REPO
        env["HVDRUN_FORCE_LOCAL"] = "1"
        env["HOROVOD_CYCLE_TIME"] = "1"
        handle = spawn_worker(slot, [sys.executable, str(script)], env,
                              prefix_output=False)
        return handle.proc

    try:
        driver.start(create_worker)
        code = driver.wait(timeout=timeout)
        results = {}
        for key in ("hostA:0", "hostB:0"):
            blob = server.handle_get(f"test_results/{key}")
            if blob is not None:
                results[key] = pickle.loads(blob)
        return code, results
    finally:
        driver.stop()
        server.stop()
        os.environ.pop("HVDRUN_FORCE_LOCAL", None)


def test_elastic_host_added_mid_training(tmp_path):
    """Start with one host; a second appears mid-run. Training must
    continue through the reset and finish at size 2."""
    phase2 = tmp_path / "phase2"
    script = tmp_path / "discover.sh"
    script.write_text(
        f"#!/bin/sh\necho hostA:1\n[ -f {phase2} ] && echo hostB:1\nexit 0\n"
    )
    script.chmod(0o755)

    code, results = _run_elastic(
        tmp_path, str(script), min_np=1, max_np=2,
        worker_env={"TEST_TOTAL_BATCHES": "120"},
        # Event-driven: hostB appears only once hostA's worker is up and
        # training, so batches remain for the post-reset size-2 phase no
        # matter how slow worker startup was.
        on_worker_meshed=phase2.touch,
    )
    assert code == 0, code
    assert "hostA:0" in results
    rank, hist = results["hostA:0"]
    sizes = {s for _, s in hist}
    assert 1 in sizes and 2 in sizes, sizes
    assert "hostB:0" in results  # the added worker also finished


def test_elastic_fault_tolerance_worker_death(tmp_path):
    """Two hosts; hostB's worker kills itself mid-run. The driver must
    blacklist hostB and the survivor finishes alone."""
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho hostA:1\necho hostB:1\n")
    script.chmod(0o755)
    sentinel = tmp_path / "failed_once"

    code, results = _run_elastic(
        tmp_path, str(script), min_np=1, max_np=2,
        worker_env={
            "TEST_TOTAL_BATCHES": "30",
            "TEST_FAIL_KEY": "hostB:0",
            "TEST_FAIL_SENTINEL": str(sentinel),
        },
    )
    assert code == 0, code
    assert sentinel.exists()  # the failure really happened
    assert "hostA:0" in results
    rank, hist = results["hostA:0"]
    sizes = [s for _, s in hist]
    assert 2 in sizes and sizes[-1] == 1, sizes  # shrank to 1 and finished


def test_elastic_gspmd_traced_step_across_topology_change(tmp_path):
    """Elastic over the traced/GSPMD surface (ref: common/elastic.py:
    147-168): the training step is a wrap_step-jitted SPMD function over
    a local 2-device mesh (XLA psum inside shard_map), composed with the
    engine's cross-worker allreduce. A host added mid-run must force a
    mesh rebuild + RETRACE (world size is baked into the trace) with the
    JaxState pytree carried through, and every worker must converge to
    identical weights."""
    phase2 = tmp_path / "phase2"
    script = tmp_path / "discover.sh"
    script.write_text(
        f"#!/bin/sh\necho hostA:1\n[ -f {phase2} ] && echo hostB:1\nexit 0\n"
    )
    script.chmod(0o755)

    code, results = _run_elastic(
        tmp_path, str(script), min_np=1, max_np=2,
        worker_env={"TEST_TOTAL_BATCHES": "40"},
        on_worker_meshed=phase2.touch,
        worker_src=_GSPMD_WORKER,
    )
    assert code == 0, code
    assert "hostA:0" in results and "hostB:0" in results

    rank_a, (hist_a, params_a) = results["hostA:0"]
    rank_b, (hist_b, params_b) = results["hostB:0"]

    # The topology really changed mid-run...
    sizes_a = [s for _, s, _ in hist_a]
    assert 1 in sizes_a and 2 in sizes_a, sizes_a
    # ...and the size change forced a retrace: the step's python body
    # ran again after the reset (trace counter bumped post-change).
    traces_at_size1 = {t for _, s, t in hist_a if s == 1}
    traces_at_size2 = {t for _, s, t in hist_a if s == 2}
    assert traces_at_size2 and max(traces_at_size2) > max(traces_at_size1), (
        hist_a
    )

    # State carried: batches continued past the reset up to TOTAL.
    assert len(hist_a) >= 40, len(hist_a)

    # Both workers end with identical, trained weights (the pytree was
    # re-synced into the grown world and updates stayed consistent).
    import numpy as np

    np.testing.assert_allclose(params_a, params_b, rtol=1e-5)
    w_true = np.array([1.0, 2.0, -1.0, 0.5])
    assert np.abs(np.asarray(params_a) - w_true).mean() < np.abs(w_true).mean(), (
        params_a
    )
