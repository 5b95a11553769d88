#!/usr/bin/env python
"""Chaos smoke: kill — or wedge — one of N local workers mid-step.

Reproduces the fault-tolerance acceptance scenarios outside pytest:
spawn N process-mode workers allreducing in a loop, arm a deterministic
fault-injection rule on one rank, and report how every survivor died.

Default (kill) mode — tests/test_fault_tolerance.py's scenario: the
doomed rank ``os._exit``\\s at a step; success means every survivor
exited through HorovodInternalError within 2x
``HOROVOD_TCP_TIMEOUT_SECONDS`` — no hang, no raw ConnectionError.

``--wedge`` mode — tests/test_health.py's scenario: the doomed rank
FREEZES (process alive, sockets open, heartbeats stop) with
``HOROVOD_TCP_TIMEOUT_SECONDS=0`` (unbounded), the hang only the
liveness plane can bound. Success means every survivor raised
HorovodInternalError NAMING the wedged rank within
``miss_limit x interval`` (+ slack), while the wedged process itself
stayed alive until this script killed it.

``--killall`` mode — whole-job loss, the scenario the elastic plane
alone cannot survive and the durability plane (docs/checkpoint.md)
exists for: EVERY rank dies at the kill step (rendezvous server
included), then a fresh job over the same checkpoint dir must resume
at the last committed checkpoint with bitwise state parity. Delegates
to ``checkpoint_smoke``'s two-phase harness.

``--serving`` mode — the serving plane's wedge scenario
(docs/serving.md): a 4-rank continuous-batching serving mesh under
concurrent HTTP load has one replica wedged mid-traffic; the liveness
verdict evicts it, survivors re-mesh and every accepted request still
completes. Delegates to ``serving_smoke``'s harness (its phase 3).
Add ``--killdoor N`` to instead hard-kill the ACTIVE front door of a
two-door fleet after N admissions (serving_smoke phases 4-5): the
standby door must win the failover election with zero accepted-request
loss.

    python scripts/chaos_smoke.py                 # 4 workers, kill rank 2 at step 3
    python scripts/chaos_smoke.py --np 8 --kill-rank 5 --kill-step 10
    python scripts/chaos_smoke.py --wedge         # wedge rank 2 instead
    python scripts/chaos_smoke.py --wedge --hb-interval 0.5 --hb-miss 4
    python scripts/chaos_smoke.py --killall --kill-step 7
    python scripts/chaos_smoke.py --serving       # wedge a serving replica
    python scripts/chaos_smoke.py --serving --killdoor 5  # kill the active door
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import fault_injection
    from horovod_tpu.common.exceptions import HorovodInternalError

    STEPS = int(os.environ["CHAOS_STEPS"])
    VERDICT = os.environ.get("CHAOS_VERDICT_FILE")

    def verdict(text):
        if VERDICT:
            with open(VERDICT, "w") as f:
                f.write(text)

    hvd.init()
    rank = hvd.rank()
    try:
        for step in range(STEPS):
            hvd.allreduce(np.ones(8, np.float32), name="g")
            fault_injection.advance_step()
            if step % 10 == 0:
                print(f"rank {rank}: step {step}", flush=True)
        print(f"rank {rank}: completed all {STEPS} steps", flush=True)
        verdict("completed")
        sys.exit(0)
    except HorovodInternalError as e:
        print(f"rank {rank}: HorovodInternalError: {e}", flush=True)
        verdict(str(e))
        sys.exit(42)
    except ConnectionError as e:
        print(f"rank {rank}: RAW ConnectionError LEAKED: {e}", flush=True)
        verdict(f"RAW: {e}")
        sys.exit(13)
""")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--np", dest="np_", type=int, default=4,
                    help="world size (default 4)")
    ap.add_argument("--kill-rank", type=int, default=2,
                    help="rank to kill/wedge (default 2)")
    ap.add_argument("--kill-step", type=int, default=3)
    ap.add_argument("--steps", type=int, default=50,
                    help="total training steps per worker")
    ap.add_argument("--timeout", type=float, default=5.0,
                    help="HOROVOD_TCP_TIMEOUT_SECONDS for kill mode")
    ap.add_argument("--wedge", action="store_true",
                    help="wedge (freeze) the doomed rank instead of "
                         "killing it, with unbounded TCP timeouts — "
                         "exercises heartbeat detection")
    ap.add_argument("--hb-interval", type=float, default=0.5,
                    help="HOROVOD_HEARTBEAT_INTERVAL_SECONDS (wedge mode)")
    ap.add_argument("--hb-miss", type=int, default=4,
                    help="HOROVOD_HEARTBEAT_MISS_LIMIT (wedge mode)")
    ap.add_argument("--killall", action="store_true",
                    help="kill EVERY rank at --kill-step (whole-job "
                         "loss) and assert a restarted job resumes "
                         "from the last committed durable checkpoint "
                         "with bitwise parity")
    ap.add_argument("--serving", action="store_true",
                    help="wedge one replica of a 4-rank serving mesh "
                         "under concurrent HTTP load; the verdict "
                         "evicts it and every accepted request still "
                         "completes (docs/serving.md)")
    ap.add_argument("--killdoor", type=int, default=None, metavar="N",
                    help="with --serving: run ONLY the fleet phases — "
                         "a killdoor:after=N chaos rule hard-kills the "
                         "ACTIVE front door after N admissions; the "
                         "standby door must win the election with zero "
                         "accepted-request loss (docs/serving.md "
                         "\"Failure drills\")")
    ap.add_argument("--interval", type=int, default=2,
                    help="HOROVOD_CHECKPOINT_INTERVAL_STEPS "
                         "(killall mode)")
    ap.add_argument("--transport", choices=["tcp", "shm"], default="tcp",
                    help="shm: data-plane frames between the co-located "
                         "workers ride the shared-memory overlay "
                         "(HOROVOD_TRANSPORT=auto) while heartbeats "
                         "stay on TCP — proves kill/wedge detection "
                         "and root-cause attribution hold when the "
                         "dead peer is reached over shared memory")
    args = ap.parse_args()

    if args.killall:
        return run_killall(args)
    if args.serving:
        return run_serving(args)

    from horovod_tpu.runner.hosts import get_host_assignments, parse_hosts
    from horovod_tpu.runner.launch import slot_env
    from horovod_tpu.runner.rendezvous_server import RendezvousServer

    server = RendezvousServer()
    port = server.start()
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(WORKER)

        slots = get_host_assignments(
            parse_hosts(f"localhost:{args.np_}"), args.np_)
        procs = {}
        verdict_files = {}
        try:
            for slot in slots:
                env = dict(os.environ)
                env.update(slot_env(slot, "127.0.0.1", port))
                env["PYTHONPATH"] = REPO
                env["HVDRUN_FORCE_LOCAL"] = "1"
                env["HOROVOD_CYCLE_TIME"] = "1"
                env["CHAOS_STEPS"] = str(args.steps)
                verdict_files[slot.rank] = os.path.join(
                    td, f"verdict_{slot.rank}")
                env["CHAOS_VERDICT_FILE"] = verdict_files[slot.rank]
                env.pop("HOROVOD_FAULT_INJECT", None)
                if args.transport == "shm":
                    env["HOROVOD_TRANSPORT"] = "auto"
                if args.wedge:
                    # The headline scenario: UNBOUNDED socket I/O — only
                    # the liveness plane bounds detection.
                    env["HOROVOD_TCP_TIMEOUT_SECONDS"] = "0"
                    env["HOROVOD_HEARTBEAT_INTERVAL_SECONDS"] = str(
                        args.hb_interval)
                    env["HOROVOD_HEARTBEAT_MISS_LIMIT"] = str(args.hb_miss)
                else:
                    env["HOROVOD_TCP_TIMEOUT_SECONDS"] = str(args.timeout)
                if slot.rank == args.kill_rank:
                    action = "wedge" if args.wedge else "kill"
                    env["HOROVOD_FAULT_INJECT"] = \
                        f"{action}:step={args.kill_step}"
                procs[slot.rank] = subprocess.Popen(
                    [sys.executable, script], env=env)
            mode = "wedges" if args.wedge else "dies"
            print(f"spawned {args.np_} workers; rank {args.kill_rank} "
                  f"{mode} at step {args.kill_step}", flush=True)

            if args.wedge:
                return run_wedge(args, procs, verdict_files)
            return run_kill(args, procs)
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            server.stop()


def run_killall(args) -> int:
    """Whole-job loss + recovery. The kill rule is armed on EVERY rank
    (``kill:step=K`` with no rank= filter), so nothing survives — not
    even the rendezvous KV. checkpoint_smoke's harness then restarts
    the job from nothing but the shared checkpoint dir and asserts a
    bitwise resume at the last committed step, bitwise-identical final
    weights, and zero partial-checkpoint debris."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import checkpoint_smoke

    if args.kill_step <= args.interval:
        print(f"FAIL: --kill-step {args.kill_step} <= --interval "
              f"{args.interval}: no checkpoint can commit before the "
              "kill", flush=True)
        return 2
    return checkpoint_smoke.run_killall(args)


def run_serving(args) -> int:
    """Serving-plane chaos: delegate to serving_smoke's harness with
    the same wedge knobs this script uses (docs/serving.md). With
    --killdoor N only the fleet phases run: the active front door is
    hard-killed after N admissions and the standby door must take over
    with zero accepted-request loss."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serving_smoke

    sys.argv = ["serving_smoke",
                "--np", str(args.np_),
                "--wedge-rank", str(args.kill_rank),
                "--hb-interval", str(args.hb_interval),
                "--hb-miss", str(args.hb_miss)]
    if args.killdoor is not None:
        sys.argv += ["--fleet-only", "--killdoor-after",
                     str(args.killdoor)]
    return serving_smoke.main()


def run_kill(args, procs) -> int:
    t_death = None
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if procs[args.kill_rank].poll() is not None:
            t_death = time.monotonic()
            break
        time.sleep(0.1)
    if t_death is None:
        print("FAIL: doomed worker never died", flush=True)
        return 2
    print(f"rank {args.kill_rank} died "
          f"(exit {procs[args.kill_rank].returncode})", flush=True)

    budget = 2 * args.timeout + 30
    ok = True
    for rank, proc in sorted(procs.items()):
        if rank == args.kill_rank:
            continue
        remaining = budget - (time.monotonic() - t_death)
        try:
            proc.wait(timeout=max(remaining, 1.0))
        except subprocess.TimeoutExpired:
            print(f"FAIL: rank {rank} HUNG past {budget:.0f}s",
                  flush=True)
            ok = False
            continue
        verdict = {42: "clean HorovodInternalError",
                   0: "completed (died pre-mesh?)",
                   13: "RAW ConnectionError (FORBIDDEN)"}.get(
                       proc.returncode, "unexpected")
        print(f"rank {rank}: exit {proc.returncode} ({verdict})",
              flush=True)
        ok = ok and proc.returncode == 42
    print("PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


def run_wedge(args, procs, verdict_files) -> int:
    window = args.hb_interval * args.hb_miss
    # Survivors must fail within the detection window (+ generous slack
    # for oversubscribed CI boxes); the wedged process must stay ALIVE.
    budget = window + 60
    deadline = time.monotonic() + 120 + budget
    ok = True
    rows = []
    for rank, proc in sorted(procs.items()):
        if rank == args.kill_rank:
            continue
        try:
            proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            rows.append((rank, "HUNG", "survivor hung past the "
                         "heartbeat window (liveness plane broken)"))
            ok = False
            continue
        msg = ""
        try:
            with open(verdict_files[rank]) as f:
                msg = f.read()
        except OSError:
            pass
        named = f"rank {args.kill_rank}" in msg and "declared dead" in msg
        clean = proc.returncode == 42
        rows.append((rank, f"exit {proc.returncode}",
                     msg if msg else "(no verdict written)"))
        ok = ok and clean and named
    if procs[args.kill_rank].poll() is not None:
        print(f"FAIL: wedged rank {args.kill_rank} DIED "
              f"(exit {procs[args.kill_rank].returncode}) — a wedge must "
              "keep the process alive", flush=True)
        ok = False
    else:
        print(f"wedged rank {args.kill_rank} is alive and frozen, as "
              "intended (killing it now)", flush=True)

    print(f"\nper-rank verdicts (window {window:.1f}s = "
          f"{args.hb_miss} x {args.hb_interval:g}s):", flush=True)
    for rank, status, msg in rows:
        print(f"  rank {rank}: {status}: {msg}", flush=True)
    print("PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
