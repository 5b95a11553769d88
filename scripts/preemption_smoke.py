#!/usr/bin/env python
"""Preemption-drain smoke: an ANNOUNCED preemption must beat an
unannounced failure on every axis the drain plane promises
(docs/fault_tolerance.md "Announced preemption").

Phase 1 (graceful): four elastic workers train with a checkpoint
interval far larger than the run (so ONLY the drain's forced
checkpoint can produce a manifest); one worker receives the preemption
signal mid-run (``preempt:step=N`` chaos rule). Asserts:

  * the drained worker's final commit is durable — a complete manifest
    exists at step >= the preemption step (zero lost steps beyond the
    checkpoint interval, which never fired);
  * survivors finish at np=3 with the disruption attributed to the
    ``preemption`` badput bucket — the ``failure`` bucket stays 0;
  * the drained host collects no blacklist strike (the exit was the
    plan), and the driver exits 0.

Phase 2 (timeout comparison): the same scenario, but the worker
WEDGES (unannounced: process alive, heartbeats stop) so recovery must
wait out the liveness timeout. The run emits one JSON line comparing
the two goodput ratios; graceful must beat timeout.

    python scripts/preemption_smoke.py
    python scripts/preemption_smoke.py --preempt-host hostC --batches 12
"""
from __future__ import annotations

import argparse
import importlib.util
import json
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
            fault_injection.advance_step()  # doomed worker preempts/wedges
            state.history.append((hvd.rank(), hvd.size()))
            state.batch += 1
            state.commit()
            time.sleep(0.05)
        return list(state.history)

    hist = train(state)
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


def run_phase(args, fault_spec: str, ckpt_dir: str | None,
              events_dir: str | None = None):
    """One driver+4 workers run; returns (exit_code, results_by_host,
    driver) with the driver already stopped."""
    from horovod_tpu.common import events as events_mod
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.launch import slot_env, spawn_worker
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    if events_dir is not None:
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
            env["HOROVOD_TCP_TIMEOUT_SECONDS"] = "0"  # unbounded: the point
            env["HOROVOD_HEARTBEAT_INTERVAL_SECONDS"] = str(args.hb_interval)
            env["HOROVOD_HEARTBEAT_MISS_LIMIT"] = str(args.hb_miss)
            env["SMOKE_TOTAL_BATCHES"] = str(args.batches)
            env.pop("HOROVOD_FAULT_INJECT", None)
            if ckpt_dir is not None:
                env["HOROVOD_CHECKPOINT_DIR"] = ckpt_dir
                # Interval >> batches: the only way a manifest appears
                # is the drain's forced save_now.
                env["HOROVOD_CHECKPOINT_INTERVAL_STEPS"] = "1000"
            if events_dir is not None:
                env["HOROVOD_EVENTS_DIR"] = events_dir
                env["HOROVOD_EVENTS_SPOOL_SECONDS"] = "0.1"
            if slot.hostname == args.preempt_host:
                env["HOROVOD_FAULT_INJECT"] = fault_spec
            handle = spawn_worker(slot, [sys.executable, script], env,
                                  prefix_output=False)
            return handle.proc

        try:
            driver.start(create_worker)
            code = driver.wait(timeout=args.deadline)
            results = {}
            for h in HOSTS:
                blob = server.handle_get(f"smoke_results/{h}:0")
                if blob is not None:
                    results[h] = pickle.loads(blob)
            return code, results, driver
        finally:
            driver.stop()
            server.stop()
            rec = events_mod.active()
            if events_dir is not None and rec is not None:
                rec.flush_spool()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preempt-host", default="hostC")
    ap.add_argument("--preempt-step", type=int, default=3)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--deadline", type=float, default=240.0,
                    help="wall-clock bound per phase")
    ap.add_argument("--hb-interval", type=float, default=0.5)
    ap.add_argument("--hb-miss", type=int, default=4)
    ap.add_argument("--ready-timeout", type=float, default=8.0)
    args = ap.parse_args()

    os.environ["HVDRUN_FORCE_LOCAL"] = "1"
    os.environ["HOROVOD_ELASTIC_READY_TIMEOUT"] = str(args.ready_timeout)
    os.environ["HOROVOD_DRAIN_GRACE_SECONDS"] = "15"

    from horovod_tpu.common.checkpoint import find_latest_manifest

    survivors = [h for h in HOSTS if h != args.preempt_host]
    ok = True

    # -- phase 1: announced preemption, graceful drain -----------------
    print("=== phase 1: graceful (announced preemption) ===", flush=True)
    with tempfile.TemporaryDirectory() as ckpt_dir, \
            tempfile.TemporaryDirectory() as events_dir:
        t0 = time.monotonic()
        code, results, driver = run_phase(
            args, f"preempt:step={args.preempt_step}", ckpt_dir,
            events_dir=events_dir)
        graceful_s = time.monotonic() - t0
        # The lifecycle chronicle (docs/events.md): merging every
        # journal must reconstruct the drill as one causal narrative.
        report = _incident_report().build_report([events_dir])
        kinds = [d["kind"] for d in report["events"]]
        print(f"chronicle: {len(kinds)} events from ranks "
              f"{report['summary']['ranks']}", flush=True)
        if not _ordered(kinds, "drain.notice", "drain.commit_barrier",
                        "drain.drained"):
            print("FAIL: chronicle lost the drain protocol order "
                  "(notice -> commit barrier -> drained): "
                  f"{kinds}", flush=True)
            ok = False
        # The manifest finalize (rank 0) races the drained rank's exit
        # — it only needs that rank's shard, not its liveness — so the
        # durability claim is barrier -> commit, not drained -> commit.
        if not _ordered(kinds, "drain.notice", "drain.commit_barrier",
                        "ckpt.commit"):
            print("FAIL: chronicle lost the durability order "
                  "(notice -> commit barrier -> ckpt.commit): "
                  f"{kinds}", flush=True)
            ok = False
        # Driver reaction: quarantine on the notice, then the shrunk
        # re-mesh. (No elastic.evict here: on a clean drain exit the
        # worker-exit activation re-meshes before the grace window
        # ends, and survivors restore/reset under the OLD epoch before
        # the new epoch's remesh — exactly what the sort shows.)
        if not _ordered(kinds, "drain.notice", "host.quarantine",
                        "elastic.remesh"):
            print("FAIL: chronicle lost the driver reaction order "
                  f"(notice -> quarantine -> remesh): {kinds}", flush=True)
            ok = False
        if not _ordered(kinds, "elastic.restore", "elastic.reset",
                        "elastic.remesh"):
            print("FAIL: chronicle lost the recovery order "
                  f"(restore -> reset -> remesh): {kinds}", flush=True)
            ok = False
        restores = [d for d in report["events"]
                    if d["kind"] == "elastic.restore"]
        if not any((d.get("attrs") or {}).get("peer_drained")
                   for d in restores):
            print("FAIL: no survivor's elastic.restore was attributed "
                  f"to a draining peer: {restores}", flush=True)
            ok = False
        if "drain.peer" not in kinds:
            print("FAIL: no survivor journaled drain.peer", flush=True)
            ok = False
        if code != 0:
            print(f"FAIL: graceful phase driver exit {code}", flush=True)
            ok = False
        found = find_latest_manifest(ckpt_dir)
        if found is None:
            print("FAIL: no manifest — the drain's forced checkpoint "
                  "never committed", flush=True)
            ok = False
            manifest_step = None
        else:
            manifest_step, manifest, _ = found
            print(f"drain checkpoint: manifest at step {manifest_step} "
                  f"({len(manifest['shards'])} shards)", flush=True)
            if manifest_step < args.preempt_step:
                print(f"FAIL: manifest step {manifest_step} < preemption "
                      f"step {args.preempt_step}: steps were lost",
                      flush=True)
                ok = False
            if len(manifest["shards"]) != len(HOSTS):
                print(f"FAIL: drain manifest has "
                      f"{len(manifest['shards'])} shards, expected "
                      f"{len(HOSTS)} — the doomed rank's shard is not the "
                      "one that committed", flush=True)
                ok = False
        graceful_ratio = None
        for h in survivors:
            doc = results.get(h)
            if doc is None:
                print(f"FAIL: survivor {h} reported no result", flush=True)
                ok = False
                continue
            hist, gp = doc["hist"], doc["goodput"]
            preempt_bad = gp["badput"]["preemption_seconds"]
            failure_bad = gp["badput"]["restart_downtime_seconds"]
            ratio = gp["goodput"]["ratio"]
            print(f"{h}: np={hist[-1][1]} preemption badput "
                  f"{preempt_bad:.2f}s failure badput {failure_bad:.2f}s",
                  flush=True)
            if hist[-1][1] != 3:
                print(f"FAIL: survivor {h} finished at np={hist[-1][1]}, "
                      "not 3", flush=True)
                ok = False
            if preempt_bad <= 0:
                print(f"FAIL: survivor {h} recorded no preemption badput",
                      flush=True)
                ok = False
            if failure_bad > 0:
                print(f"FAIL: survivor {h} attributed the announced drain "
                      f"to the failure bucket ({failure_bad:.2f}s)",
                      flush=True)
                ok = False
            if ratio is not None and (graceful_ratio is None
                                      or ratio < graceful_ratio):
                graceful_ratio = ratio  # worst survivor = honest bound
        if driver.host_manager.blacklist_strikes(args.preempt_host):
            print(f"FAIL: drained host {args.preempt_host} collected a "
                  "blacklist strike", flush=True)
            ok = False

    # -- phase 2: unannounced wedge, liveness-timeout recovery ---------
    print("=== phase 2: timeout (unannounced wedge) ===", flush=True)
    t0 = time.monotonic()
    code, results, _ = run_phase(
        args, f"wedge:step={args.preempt_step}", None)
    timeout_s = time.monotonic() - t0
    if code != 0:
        print(f"FAIL: timeout phase driver exit {code}", flush=True)
        ok = False
    timeout_ratio = None
    for h in survivors:
        doc = results.get(h)
        if doc is None:
            print(f"FAIL: survivor {h} reported no result (timeout phase)",
                  flush=True)
            ok = False
            continue
        ratio = doc["goodput"]["goodput"]["ratio"]
        if ratio is not None and (timeout_ratio is None
                                  or ratio < timeout_ratio):
            timeout_ratio = ratio

    # -- the comparison line -------------------------------------------
    line = {
        "graceful_goodput_ratio": graceful_ratio,
        "timeout_goodput_ratio": timeout_ratio,
        "graceful_wall_seconds": round(graceful_s, 1),
        "timeout_wall_seconds": round(timeout_s, 1),
        "manifest_step": manifest_step,
        "preempt_step": args.preempt_step,
    }
    print("PREEMPTION_SMOKE " + json.dumps(line), flush=True)
    if graceful_ratio is None or timeout_ratio is None:
        print("FAIL: missing a goodput ratio for the comparison",
              flush=True)
        ok = False
    elif graceful_ratio <= timeout_ratio:
        print(f"FAIL: graceful goodput ratio {graceful_ratio:.3f} did not "
              f"beat the timeout path {timeout_ratio:.3f}", flush=True)
        ok = False
    print("PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
