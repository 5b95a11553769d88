#!/usr/bin/env python
"""Elastic-recovery smoke: wedge 1 of 4 elastic workers, assert the
survivors resume at np=3 within the deadline.

The CI-runnable version of the liveness-plane acceptance scenario
(tests/test_health.py::test_chaos_wedge_elastic_recovery_and_hang_control,
minus the hang control): four local workers under a real ElasticDriver,
``HOROVOD_TCP_TIMEOUT_SECONDS=0`` (unbounded), one worker FREEZES
mid-step (``wedge`` fault rule: process alive, sockets open, heartbeats
stop). The heartbeat plane must declare it dead, the driver must evict
its slot at the ready deadline and blacklist its host, and the three
survivors must finish training at np=3 — all inside ``--deadline``
seconds.

    python scripts/elastic_smoke.py
    python scripts/elastic_smoke.py --wedge-host hostA --deadline 180
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import pickle
import sys
import tempfile
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _incident_report():
    spec = importlib.util.spec_from_file_location(
        "incident_report",
        os.path.join(REPO, "scripts", "incident_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ordered(kinds, *want) -> bool:
    """True when `want` appears as an ordered subsequence of kinds."""
    i = 0
    for w in want:
        try:
            i = kinds.index(w, i) + 1
        except ValueError:
            return False
    return True

WORKER = textwrap.dedent("""
    import os, pickle, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.backend.elastic_env import spawn_identity
    from horovod_tpu.backend.rendezvous import RendezvousClient
    from horovod_tpu.common import fault_injection
    from horovod_tpu.elastic.state import ObjectState
    from horovod_tpu.utils import env as env_cfg

    TOTAL = int(os.environ["SMOKE_TOTAL_BATCHES"])
    hvd.init()
    state = ObjectState(batch=0, history=[])

    @hvd.elastic.run
    def train(state):
        while state.batch < TOTAL:
            hvd.allreduce(np.ones(2, np.float32), name="g")
            fault_injection.advance_step()   # the doomed worker wedges here
            state.history.append((hvd.rank(), hvd.size()))
            state.batch += 1
            state.commit()
            time.sleep(0.05)
        return list(state.history)

    hist = train(state)
    # Goodput plane (docs/goodput.md): the eviction's disruption window
    # (failure -> re-meshed training) must have landed in the ledger's
    # restart-badput bucket on every survivor.
    from horovod_tpu.common import goodput
    gp = goodput.active().view()
    rdv = RendezvousClient(env_cfg.get_str(env_cfg.RENDEZVOUS_ADDR),
                           env_cfg.get_int(env_cfg.RENDEZVOUS_PORT, 0))
    rdv.put("smoke_results", spawn_identity(),
            pickle.dumps({"hist": hist, "goodput": gp}))
    print(f"worker {spawn_identity()} done as rank {hvd.rank()} "
          f"size {hvd.size()}", flush=True)
""")

HOSTS = ["hostA", "hostB", "hostC", "hostD"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--wedge-host", default="hostC",
                    help="logical host whose worker wedges (default hostC)")
    ap.add_argument("--wedge-step", type=int, default=3)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--deadline", type=float, default=240.0,
                    help="wall-clock bound on the whole scenario")
    ap.add_argument("--hb-interval", type=float, default=0.5)
    ap.add_argument("--hb-miss", type=int, default=4)
    ap.add_argument("--ready-timeout", type=float, default=8.0,
                    help="HOROVOD_ELASTIC_READY_TIMEOUT for the driver")
    args = ap.parse_args()

    from horovod_tpu.common import events as events_mod
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.launch import slot_env, spawn_worker
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    os.environ["HVDRUN_FORCE_LOCAL"] = "1"
    os.environ["HOROVOD_ELASTIC_READY_TIMEOUT"] = str(args.ready_timeout)
    events_dir = tempfile.mkdtemp(prefix="hvd_events_")
    # The driver journals lifecycle events as rank -1
    # (events_driver.jsonl); workers get the dir via env below.
    events_mod.set_current(events_mod.EventRecorder(
        rank=-1, spool_dir=events_dir, spool_seconds=0.1))
    server = RendezvousServer()
    port = server.start()
    driver = ElasticDriver(server, FixedHosts({h: 1 for h in HOSTS}),
                           min_np=2, max_np=4, poll_interval=0.25)

    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(WORKER)

        def create_worker(slot, extra_env):
            env = slot_env(slot, "127.0.0.1", port, elastic=True)
            env.update(extra_env)
            env["PYTHONPATH"] = REPO
            env["HVDRUN_FORCE_LOCAL"] = "1"
            env["HOROVOD_CYCLE_TIME"] = "1"
            env["HOROVOD_TCP_TIMEOUT_SECONDS"] = "0"   # unbounded: the point
            env["HOROVOD_HEARTBEAT_INTERVAL_SECONDS"] = str(args.hb_interval)
            env["HOROVOD_HEARTBEAT_MISS_LIMIT"] = str(args.hb_miss)
            env["SMOKE_TOTAL_BATCHES"] = str(args.batches)
            env["HOROVOD_EVENTS_DIR"] = events_dir
            env["HOROVOD_EVENTS_SPOOL_SECONDS"] = "0.1"
            env.pop("HOROVOD_FAULT_INJECT", None)
            if slot.hostname == args.wedge_host:
                env["HOROVOD_FAULT_INJECT"] = f"wedge:step={args.wedge_step}"
            handle = spawn_worker(slot, [sys.executable, script], env,
                                  prefix_output=False)
            return handle.proc

        t0 = time.monotonic()
        try:
            driver.start(create_worker)
            code = driver.wait(timeout=args.deadline)
            elapsed = time.monotonic() - t0
            if code != 0:
                print(f"FAIL: driver exit {code} after {elapsed:.0f}s "
                      f"(None = still hung at the deadline)", flush=True)
                return 1
            survivors = [h for h in HOSTS if h != args.wedge_host]
            ok = True
            for h in survivors:
                blob = server.handle_get(f"smoke_results/{h}:0")
                if blob is None:
                    print(f"FAIL: survivor {h} reported no result",
                          flush=True)
                    ok = False
                    continue
                doc = pickle.loads(blob)
                hist, gp = doc["hist"], doc["goodput"]
                final_np = hist[-1][1]
                downtime = gp["badput"]["restart_downtime_seconds"]
                ratio = gp["goodput"]["ratio"]
                print(f"{h}: finished batch {len(hist)} at np={final_np}, "
                      f"restart badput {downtime:.2f}s "
                      f"(goodput ratio "
                      f"{'none' if ratio is None else format(ratio, '.3f')})",
                      flush=True)
                ok = ok and final_np == 3
                # The eviction cost real wall time (detection + barrier
                # + re-mesh); it must be attributed, not lost.
                if downtime <= 0:
                    print(f"FAIL: survivor {h} recorded no restart-"
                          "badput for the eviction", flush=True)
                    ok = False
                if not (gp["goodput"]["ratio"] is not None
                        and gp["goodput"]["ratio"] < 1.0):
                    print(f"FAIL: survivor {h} goodput ratio not < 1",
                          flush=True)
                    ok = False
            if not driver.host_manager.blacklist_strikes(args.wedge_host):
                print(f"FAIL: wedged host {args.wedge_host} was never "
                      "blacklisted", flush=True)
                ok = False
            # The lifecycle chronicle (docs/events.md): merging every
            # journal must read the wedge as one causal narrative.
            events_mod.active().flush_spool()
            report = _incident_report().build_report([events_dir])
            kinds = [d["kind"] for d in report["events"]]
            print(f"chronicle: {len(kinds)} events from ranks "
                  f"{report['summary']['ranks']}", flush=True)
            # Survivors restore/reset under the OLD epoch (the failed
            # collective) before the driver's new-epoch remesh — the
            # causal sort orders the wedge exactly that way.
            if not _ordered(kinds, "health.verdict", "elastic.evict",
                            "elastic.restore", "elastic.reset",
                            "elastic.remesh"):
                print("FAIL: chronicle lost the wedge narrative "
                      "(verdict -> evict -> restore -> reset -> "
                      f"remesh): {kinds}", flush=True)
                ok = False
            if not _ordered(kinds, "elastic.evict", "host.blacklist"):
                print("FAIL: chronicle lost the strike order "
                      f"(evict -> blacklist): {kinds}", flush=True)
                ok = False
            print(f"recovered and finished at np=3 in {elapsed:.0f}s "
                  f"(deadline {args.deadline:.0f}s)" if ok else "FAIL",
                  flush=True)
            print("PASS" if ok else "FAIL", flush=True)
            return 0 if ok else 1
        finally:
            driver.stop()
            server.stop()
            import shutil

            shutil.rmtree(events_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
