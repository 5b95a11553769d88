"""Launcher package: hvdrun CLI + programmatic run() API
(ref: horovod/runner/__init__.py:90 horovod.run)."""
from __future__ import annotations

import os
import pickle
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional

from .hosts import HostInfo, SlotInfo, get_host_assignments, parse_hosts
from .launch import launch_static, make_parser, run_commandline
from .rendezvous_server import RendezvousServer


def run(
    func: Callable[[], Any],
    args=(),
    kwargs=None,
    np: int = 1,
    hosts: Optional[str] = None,
    extra_env: Optional[Dict[str, str]] = None,
    verbose: bool = False,
) -> List[Any]:
    """Run `func` on np processes; returns per-rank results in rank order
    (ref: horovod/runner/__init__.py:90 `horovod.run`). The function is
    pickled (cloudpickle when available) and shipped to workers; results
    come back through the rendezvous KV.

    Workers inherit the caller's platform exactly as `hvdrun`'s do
    (pass `extra_env={"JAX_PLATFORMS": "cpu"}` for host-only workers).
    A chip belongs to one process at a time: a caller that has already
    opened the chip and asks for chip workers gets the workers' own
    start-up error."""
    try:
        import cloudpickle as pickler
    except ImportError:  # pragma: no cover
        pickler = pickle

    import functools

    payload = pickler.dumps(
        functools.partial(func, *args, **(kwargs or {}))
    )
    host_list = parse_hosts(hosts) if hosts else [HostInfo("localhost", np)]
    slots = get_host_assignments(host_list, np, np)

    from .util import secret as secret_util

    server = RendezvousServer(secret_key=secret_util.make_secret_key())
    server.start()
    try:
        with tempfile.NamedTemporaryFile(suffix=".pkl", delete=False) as f:
            f.write(payload)
            func_path = f.name
        command = [sys.executable, "-m", "horovod_tpu.runner.task_runner",
                   func_path]
        env = dict(extra_env or {})
        # Prepend the repo root but keep the parent's PYTHONPATH — user
        # functions may need it to unpickle/import on workers (spawn_worker
        # overlays this env on os.environ, so dropping it here loses it).
        inherited = os.environ.get("PYTHONPATH")
        parts = (
            [os.path.dirname(os.path.dirname(os.path.dirname(__file__)))]
            + sys.path[1:2]
            + ([inherited] if inherited else [])
        )
        env.setdefault("PYTHONPATH", os.pathsep.join(parts))
        rc = launch_static(slots, command, env, verbose, rendezvous=server,
                           prefix_output=not verbose)
        if rc != 0:
            raise RuntimeError(f"hvdrun function job failed with exit code {rc}")
        results = []
        for r in range(np):
            blob = server.handle_get(f"results/{r}")
            if blob is None:
                raise RuntimeError(f"rank {r} produced no result")
            results.append(pickle.loads(blob))
        return results
    finally:
        server.stop()
        try:
            os.unlink(func_path)
        except OSError:
            pass
