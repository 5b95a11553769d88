"""horovod_tpu.spark.run — launch distributed training inside Spark
tasks (ref: horovod/spark/runner.py:195-301 run / :303 run_elastic).

Orchestration (mirrors the reference's shape):
  1. driver starts a rendezvous/KV server;
  2. one Spark task per rank executes `_task_fn` (barrier-stage
     semantics when available): each task registers its host, receives
     its slot assignment, sets the HOROVOD_* env, runs the user fn, and
     ships the pickled result back through the KV;
  3. results return in rank order.

The Spark interaction is confined to `_mapper` + `_run_spark_job`, so
the orchestration is testable without a cluster (tests inject a mock
SparkContext) and any pyspark ≥2.4 works at runtime.
"""
from __future__ import annotations

import os
import pickle
import socket
import uuid
from typing import Any, Callable, Dict, List, Optional

from ..runner.hosts import HostInfo, SlotInfo, get_host_assignments
from ..runner.launch import slot_env
from ..runner.rendezvous_server import RendezvousServer
from ..utils.logging import get_logger

logger = get_logger()
from ..utils import env as env_cfg


def _driver_addr() -> str:
    return os.environ.get("HVDRUN_DRIVER_ADDR") or socket.gethostname()


def _task_fn(index: int, driver_addr: str, driver_port: int,
             payload: bytes, extra_env: Dict[str, str]):
    """Runs inside the Spark executor (ref: horovod/spark/task/)."""
    from ..backend.rendezvous import RendezvousClient

    client = RendezvousClient(driver_addr, driver_port, timeout=300.0)
    hostname = socket.gethostname()
    client.put("spark_hosts", str(index), hostname.encode())
    # Driver computes assignments once all tasks registered.
    row = client.wait_get("spark_assign", str(index)).decode()
    rank, size, lrank, lsize, crank, csize = (int(v) for v in row.split(","))
    slot = SlotInfo(hostname=hostname, rank=rank, local_rank=lrank,
                    cross_rank=crank, size=size, local_size=lsize,
                    cross_size=csize)
    # The launcher's env contract, chip pinning included: the task
    # inherits the executor's platform, as hvdrun's workers do.
    env = slot_env(slot, driver_addr, driver_port, extra_env)
    # Barrier tasks have never exported a slot hostname (the TCP mesh
    # then advertises loopback or HOROVOD_MESH_ADDR); keep it so.
    if env_cfg.HOSTNAME not in extra_env:
        del env[env_cfg.HOSTNAME]
    os.environ.update(env)
    fn = pickle.loads(payload)
    result = fn()
    client.put("spark_results", str(rank), pickle.dumps(result))
    return rank


def _assign_ranks(server: RendezvousServer, num_proc: int):
    """Group registered tasks by host-hash into the reference's
    rank/local/cross topology (ref: spark/runner.py:230-260 host-hash
    grouping)."""
    by_host: Dict[str, List[int]] = {}
    order: List[int] = []
    for i in range(num_proc):
        host = server.handle_get(f"spark_hosts/{i}")
        host = host.decode() if host else f"unknown-{i}"
        by_host.setdefault(host, []).append(i)
        order.append(i)
    hosts = [HostInfo(h, len(idxs)) for h, idxs in by_host.items()]
    slots = get_host_assignments(hosts, num_proc, num_proc)
    # Map slot -> task index: the k-th task on a host takes that host's
    # k-th slot.
    it = {h: list(idxs) for h, idxs in by_host.items()}
    for slot in slots:
        task_index = it[slot.hostname].pop(0)
        server.handle_put(
            f"spark_assign/{task_index}", slot.to_response_string().encode()
        )


def _run_spark_job(sc, num_proc: int, mapper, barrier: bool = True):
    """Execute mapper over num_proc partitions, barrier-mode when the
    cluster supports it (ref: spark/runner.py barrier usage).

    The ELASTIC path passes barrier=False: a barrier stage gang-
    schedules (no task starts until all max_np fit, defeating the
    min_np window) and aborts every task on a single death (defeating
    shrink-and-continue). The reference's run_elastic likewise runs a
    plain stage."""
    rdd = sc.parallelize(range(num_proc), num_proc)
    if barrier:
        try:
            return rdd.barrier().mapPartitionsWithIndex(mapper).collect()
        except AttributeError:  # pre-2.4 or mock without barrier
            pass
    return rdd.mapPartitionsWithIndex(mapper).collect()


def run(
    fn: Callable[[], Any],
    args=(),
    kwargs=None,
    num_proc: Optional[int] = None,
    extra_env: Optional[Dict[str, str]] = None,
    verbose: int = 1,
    spark_context=None,
) -> List[Any]:
    """Run `fn` on `num_proc` Spark tasks; per-rank results in rank order
    (ref: horovod/spark/runner.py:195 signature subset)."""
    import functools

    try:
        import cloudpickle as pickler
    except ImportError:
        pickler = pickle

    sc = spark_context
    if sc is None:
        try:
            from pyspark import SparkContext

            sc = SparkContext._active_spark_context
        except ImportError as e:
            raise ImportError(
                "horovod_tpu.spark.run needs pyspark (or pass "
                "spark_context=); for non-Spark clusters use "
                "horovod_tpu.runner.run"
            ) from e
        if sc is None:
            raise ValueError("no active SparkContext")
    if num_proc is None:
        num_proc = sc.defaultParallelism

    payload = pickler.dumps(functools.partial(fn, *args, **(kwargs or {})))
    server = RendezvousServer()
    port = server.start()
    addr = _driver_addr()
    env = dict(extra_env or {})

    # Driver-side assignment thread: wait for all registrations, then
    # publish the topology rows.
    import threading

    def assigner():
        import time

        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if all(
                server.handle_get(f"spark_hosts/{i}") is not None
                for i in range(num_proc)
            ):
                _assign_ranks(server, num_proc)
                return
            time.sleep(0.1)

    t = threading.Thread(target=assigner, daemon=True)
    t.start()

    def mapper(index, iterator):
        yield _task_fn(index, addr, port, payload, env)

    try:
        _run_spark_job(sc, num_proc, mapper)
        results = []
        for r in range(num_proc):
            blob = server.handle_get(f"spark_results/{r}")
            if blob is None:
                raise RuntimeError(f"rank {r} produced no result")
            results.append(pickle.loads(blob))
        return results
    finally:
        server.stop()


def run_elastic(
    fn: Callable[[], Any],
    args=(),
    kwargs=None,
    num_proc: Optional[int] = None,
    min_np: Optional[int] = None,
    max_np: Optional[int] = None,
    extra_env: Optional[Dict[str, str]] = None,
    reset_limit: Optional[int] = None,
    verbose: int = 1,
    spark_context=None,
    start_timeout: float = 600.0,
) -> List[Any]:
    """Elastic training over Spark tasks with a live min_np..max_np
    window (ref: horovod/spark/runner.py:303-404).

    `max_np` Spark tasks are launched (a plain, NON-barrier stage:
    tasks start as the cluster can schedule them, so the job begins as
    soon as `min_np` are live); each runs a task-service loop
    (`spark/elastic.py`) that heartbeats and executes worker
    spawn/kill commands from the in-driver ElasticDriver. A task dying
    mid-job shrinks the world (down to `min_np`); a task (re)appearing
    grows it — with `hvd.elastic.run` + State inside `fn` carrying
    training through each reset, exactly like host-discovery elastic
    under `hvdrun`. Results are per-rank values from the FINAL topology,
    rank order.

    `num_proc` is only the default for an unset min_np/max_np (the
    reference reads dynamic-allocation bounds the same way,
    ref: spark/runner.py:355-360); the window is what governs."""
    import functools
    import threading

    try:
        import cloudpickle as pickler
    except ImportError:
        pickler = pickle

    from ..runner.elastic.driver import ElasticDriver
    from .elastic import SparkExecDriver, SparkTaskDiscovery, \
        _elastic_task_loop

    sc = spark_context
    if sc is None:
        try:
            from pyspark import SparkContext

            sc = SparkContext._active_spark_context
        except ImportError as e:
            raise ImportError(
                "horovod_tpu.spark.run_elastic needs pyspark (or pass "
                "spark_context=)"
            ) from e
        if sc is None:
            raise ValueError("no active SparkContext")
    if num_proc is None:
        num_proc = sc.defaultParallelism
    min_np = min_np if min_np is not None else num_proc
    max_np = max_np if max_np is not None else num_proc

    payload = pickler.dumps(functools.partial(fn, *args, **(kwargs or {})))
    server = RendezvousServer()
    port = server.start()
    addr = _driver_addr()
    server.handle_put("spark_payload/fn", payload)

    env = dict(extra_env or {})

    exec_driver = SparkExecDriver(server)
    run_id = uuid.uuid4().hex[:8]

    def create_worker(slot, extra):
        wenv = slot_env(slot, addr, port, dict(env), elastic=True)
        wenv.update(extra)
        wenv["HOROVOD_CYCLE_TIME"] = os.environ.get(
            "HOROVOD_CYCLE_TIME", "1")
        # SparkProcHandle is Popen-shaped (poll/wait/terminate/kill),
        # which is all ElasticDriver requires of a worker proc.
        return exec_driver.spawn(slot.hostname, wenv, run_id)

    driver = ElasticDriver(
        server, SparkTaskDiscovery(server, max_np), min_np, max_np,
        reset_limit=reset_limit,
    )

    # Launch max_np Spark tasks running the service loop, in a thread
    # (collect() blocks until shutdown).
    def mapper(index, iterator):
        yield _elastic_task_loop(index, addr, port)

    spark_err: List[BaseException] = []

    def spark_job():
        try:
            _run_spark_job(sc, max_np, mapper, barrier=False)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            spark_err.append(e)

    spark_thread = threading.Thread(target=spark_job, daemon=True)
    spark_thread.start()

    def wait_checking_spark(timeout: float):
        """driver.wait, but a Spark-side failure surfaces IMMEDIATELY
        instead of being masked behind the full elastic timeout."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while True:
            code = driver.wait(timeout=5.0)
            if code is not None:
                return code
            if spark_err and not driver.finished:
                raise spark_err[0]
            if _time.monotonic() > deadline:
                return None

    try:
        if verbose >= 1:
            logger.info(
                "spark elastic: launching %d task services "
                "(window %d..%d)", max_np, min_np, max_np)
        driver.wait_for_available_slots(min_np, timeout=start_timeout)
        driver.start(create_worker)
        code = wait_checking_spark(timeout=start_timeout * 4)
        if code is None:
            raise RuntimeError("elastic spark job timed out")
        if code != 0:
            raise RuntimeError(
                f"elastic spark job failed with exit code {code}"
            )
        results = []
        r = 0
        while True:
            blob = server.handle_get(f"spark_results/{r}")
            if blob is None:
                break
            results.append(pickle.loads(blob))
            r += 1
        if not results:
            raise RuntimeError("no ranks produced results")
        if spark_err:
            raise spark_err[0]
        return results
    finally:
        driver.stop()
        exec_driver.shutdown()
        spark_thread.join(timeout=30)
        server.stop()
