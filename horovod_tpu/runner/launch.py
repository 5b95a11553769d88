"""`hvdrun` — the horovodrun-equivalent launcher.

(ref: horovod/runner/launch.py:715 CLI, gloo_run.py:65-258 worker
spawn/env contract.) Static launch path:

    hvdrun -np 2 python train.py
    hvdrun -np 4 -H h1:2,h2:2 python train.py

Per slot, the launcher exports the HOROVOD_RANK/SIZE/LOCAL_*/CROSS_* env
(exactly the reference's gloo env contract so `hvd.init()` picks process
mode), plus the rendezvous address of the driver's HTTP KV server the
TCP backend full-meshes through. Remote hosts launch over ssh; TPU-VM
slices are discovered from the slice's metadata env instead of NIC
probing (SURVEY.md §5.8). The launcher itself never initialises a jax
backend, and several local workers on an accelerator platform each get
one chip (`_chip_env`). Elastic mode (`--min-np/--max-np/--host-discovery-
script`) is driven by runner.elastic.driver.
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Sequence

from ..utils import env as env_cfg
from . import config_parser
from .hosts import (
    HostInfo,
    SlotInfo,
    discover_tpu_hosts,
    get_host_assignments,
    local_tpu_chips,
    parse_hostfile,
    parse_hosts,
)
from .rendezvous_server import RendezvousServer

_LOCAL_NAMES = {"localhost", "127.0.0.1", "::1"}


def is_local_host(hostname: str) -> bool:
    # HVDRUN_FORCE_LOCAL: treat every host as local — lets elastic tests
    # use distinct fake hostnames on one machine without ssh (the
    # reference's elastic integration tests do the same through ssh to
    # localhost aliases, test/integration/elastic_common.py).
    if os.environ.get("HVDRUN_FORCE_LOCAL"):
        return True
    if hostname in _LOCAL_NAMES or hostname.startswith("process-"):
        return True
    try:
        return hostname in (socket.gethostname(), socket.getfqdn())
    except OSError:  # pragma: no cover
        return False


def _chip_env(slot: SlotInfo,
              extra_env: Optional[Dict[str, str]]) -> Dict[str, str]:
    """One process for each chip: when a host runs several workers on
    an accelerator platform, local rank i gets chip i and nothing else.
    Workers inherit the platform (JAX_PLATFORMS) from the launcher's
    environment unless `extra_env` names one; only an explicit `cpu`
    turns the pinning off, because with the variable unset jax itself
    picks the TPU wherever one is attached.

    Established on libtpu 0.0.34 / v5e 2x2: all three variables are
    needed — with TPU_VISIBLE_CHIPS alone every process after the first
    aborts on libtpu's multi-process lockfile — and the process then
    sees exactly one device (which jax numbers 0 in every process)."""
    platforms = (extra_env or {}).get(
        "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
    if slot.local_size <= 1 or platforms.split(",")[0].strip() == "cpu":
        return {}
    if is_local_host(slot.hostname):
        chips = local_tpu_chips()
        if chips == 0:
            return {}  # no TPU on this host: nothing to divide
        if slot.local_size > chips:
            raise ValueError(
                f"{slot.local_size} workers on {slot.hostname} but only "
                f"{chips} TPU chip(s): a chip belongs to one process at "
                "a time — lower -np, or set JAX_PLATFORMS=cpu for "
                "host-only workers")
    return {
        "TPU_VISIBLE_CHIPS": str(slot.local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def slot_env(
    slot: SlotInfo,
    rendezvous_addr: str,
    rendezvous_port: int,
    extra_env: Optional[Dict[str, str]] = None,
    elastic: bool = False,
    secret_key: Optional[bytes] = None,
) -> Dict[str, str]:
    """The worker env contract (ref: gloo_run.py:65-198
    _slot_info_to_command) — the one place worker environments are
    built, shared by hvdrun, runner.run() and the Spark runners."""
    env = {
        env_cfg.RANK: str(slot.rank),
        env_cfg.SIZE: str(slot.size),
        env_cfg.LOCAL_RANK: str(slot.local_rank),
        env_cfg.LOCAL_SIZE: str(slot.local_size),
        env_cfg.CROSS_RANK: str(slot.cross_rank),
        env_cfg.CROSS_SIZE: str(slot.cross_size),
        env_cfg.RENDEZVOUS_ADDR: rendezvous_addr,
        env_cfg.RENDEZVOUS_PORT: str(rendezvous_port),
        env_cfg.HOSTNAME: slot.hostname,
        env_cfg.CONTROLLER: "tcp",
        env_cfg.CPU_OPERATIONS: "tcp",
    }
    if elastic:
        env[env_cfg.ELASTIC] = "1"
    if secret_key is not None:
        from .util import secret as secret_util

        env[env_cfg.SECRET_KEY] = secret_util.key_to_env(secret_key)
    env.update(_chip_env(slot, extra_env))
    if extra_env:
        env.update(extra_env)
    return env


def build_ssh_command(
    hostname: str, command: Sequence[str], env: Dict[str, str],
    ssh_port: Optional[int] = None, ssh_identity_file: Optional[str] = None,
) -> List[str]:
    """ssh invocation for a remote slot (ref: runner/util/remote.py).

    The per-job HMAC secret must never appear on a command line — it
    would be world-readable via /proc/*/cmdline on both ends for the
    whole run. When `env` carries it, the remote command instead reads
    one line from stdin into HOROVOD_SECRET_KEY; the caller writes the
    key to the ssh client's stdin (see spawn_worker)."""
    env = dict(env)
    has_secret = env_cfg.SECRET_KEY in env
    env.pop(env_cfg.SECRET_KEY, None)
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in sorted(env.items())
    )
    ssh = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_port:
        ssh += ["-p", str(ssh_port)]
    if ssh_identity_file:
        ssh += ["-i", ssh_identity_file]
    remote_cmd = f"cd {shlex.quote(os.getcwd())} && env {exports} " + " ".join(
        shlex.quote(c) for c in command
    )
    if has_secret:
        remote_cmd = (
            f"IFS= read -r {env_cfg.SECRET_KEY} && "
            f"export {env_cfg.SECRET_KEY} && " + remote_cmd
        )
    return ssh + [hostname, remote_cmd]


class WorkerHandle:
    """One launched worker. Subclasses change the transport (direct
    subprocess / ssh vs authenticated task service)."""

    def __init__(self, slot: SlotInfo, proc: subprocess.Popen):
        self.slot = slot
        self.proc = proc
        self.threads: List[threading.Thread] = []

    def poll(self) -> Optional[int]:
        return self.proc.poll()

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        return self.proc.wait(timeout=timeout)

    def terminate(self):
        # Teardown reuses the drain protocol: the configured preemption
        # signal lets workers treat launcher shutdown exactly like a
        # platform preemption notice (checkpoint-now, clean exit).
        try:
            os.killpg(os.getpgid(self.proc.pid), env_cfg.preempt_signal())
        except (ProcessLookupError, PermissionError):
            pass

    def kill(self):
        try:
            os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


class TaskServiceWorkerHandle(WorkerHandle):
    """Worker driven through a remote host's authenticated TaskService
    (ref: the reference launches remote commands through
    BasicTaskService RPC instead of a long-lived ssh per worker,
    common/service/task_service.py). `proc` is the ssh/local bootstrap
    that hosts the service; the worker command itself runs as the
    service's subprocess."""

    # RPC polls open a TCP connection each time; 4 Hz is plenty for
    # exit detection and keeps per-worker overhead trivial.
    POLL_INTERVAL = 0.25

    def __init__(self, slot: SlotInfo, proc: subprocess.Popen, client,
                 prefix_output: bool = True):
        super().__init__(slot, proc)
        self.client = client
        self._prefix = prefix_output
        self._out_off = 0
        self._rc: Optional[int] = None
        self._last_poll = 0.0

    def _emit(self, delta: bytes):
        if not self._prefix or not delta:
            return
        for line in delta.decode(errors="replace").splitlines():
            sys.stdout.write(f"[{self.slot.rank}]<stdout>:{line}\n")
        sys.stdout.flush()

    def poll(self) -> Optional[int]:
        import time as _time

        if self._rc is not None:
            return self._rc
        now = _time.monotonic()
        if now - self._last_poll < self.POLL_INTERVAL:
            return None
        self._last_poll = now
        try:
            # Offset-based: only new output crosses the wire.
            r = self.client.command_exit_code(self._out_off)
        except (ConnectionError, OSError, EOFError):
            # Service unreachable: fall back to the bootstrap process.
            rc = self.proc.poll()
            if rc is not None:
                self._rc = rc if rc != 0 else 1
            return self._rc
        self._emit(r.output)
        self._out_off += len(r.output)
        if r.terminated:
            self._rc = r.exit_code
        return self._rc

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and _time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("task-service-worker",
                                                timeout)
            _time.sleep(0.1)
        return self._rc

    def terminate(self):
        try:
            self.client.terminate()          # SIGTERM the worker command
        except (ConnectionError, OSError, EOFError, RuntimeError):
            pass
        try:
            self.client.shutdown_service()   # stop the remote service
        except (ConnectionError, OSError, EOFError, RuntimeError):
            pass
        super().terminate()  # the bootstrap ssh/local client process

    def kill(self):
        self.terminate()
        super().kill()


def _forward_stream(stream, sink, prefix: str):
    for line in iter(stream.readline, b""):
        try:
            sink.write(f"{prefix}{line.decode(errors='replace')}")
            sink.flush()
        except ValueError:  # sink closed
            break
    stream.close()


def spawn_worker(
    slot: SlotInfo,
    command: Sequence[str],
    env: Dict[str, str],
    verbose: bool = False,
    prefix_output: bool = True,
    ssh_port: Optional[int] = None,
    ssh_identity_file: Optional[str] = None,
) -> WorkerHandle:
    full_env = dict(os.environ)
    full_env.update(env)
    remote = not is_local_host(slot.hostname)
    secret = env.get(env_cfg.SECRET_KEY) if remote else None
    if remote:
        argv = build_ssh_command(slot.hostname, command, env, ssh_port,
                                 ssh_identity_file)
    else:
        argv = list(command)
    proc = subprocess.Popen(
        argv,
        env=full_env,
        stdin=subprocess.PIPE if secret else None,
        stdout=subprocess.PIPE if prefix_output else None,
        stderr=subprocess.PIPE if prefix_output else None,
        start_new_session=True,  # own process group for clean teardown
    )
    if secret:
        # The remote command's leading `read` consumes this line; the
        # key rides the encrypted channel, not the command line.
        try:
            proc.stdin.write((secret + "\n").encode())
            proc.stdin.flush()
        except (BrokenPipeError, OSError):
            pass
    handle = WorkerHandle(slot, proc)
    if prefix_output:
        # Rank-prefixed output forwarding, reference format "[1]<stdout>:"
        # (ref: gloo_run.py:149-162, safe_shell_exec.py:81-120).
        for stream, sink, tag in (
            (proc.stdout, sys.stdout, "stdout"),
            (proc.stderr, sys.stderr, "stderr"),
        ):
            t = threading.Thread(
                target=_forward_stream,
                args=(stream, sink, f"[{slot.rank}]<{tag}>:"),
                daemon=True,
            )
            t.start()
            handle.threads.append(t)
    return handle


def terminate_workers(handles: List[WorkerHandle]):
    for h in handles:
        if h.poll() is None:
            h.terminate()
    # Workers received a preemption notice (see WorkerHandle.terminate)
    # and may be writing their drain checkpoint: wait out the drain
    # grace budget, not an arbitrary 10s, before escalating to SIGKILL.
    grace = max(10.0, env_cfg.drain_grace_seconds())
    for h in handles:
        try:
            h.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            h.kill()


def launch_static(
    slots: List[SlotInfo],
    command: Sequence[str],
    extra_env: Optional[Dict[str, str]] = None,
    verbose: bool = False,
    rendezvous: Optional[RendezvousServer] = None,
    prefix_output: bool = True,
    ssh_port: Optional[int] = None,
    ssh_identity_file: Optional[str] = None,
) -> int:
    """Run one process per slot; first failure tears everything down
    (ref: gloo_run.py:243-258). Returns the first nonzero exit code or 0."""
    own_server = rendezvous is None
    if own_server:
        # Per-job shared secret: workers must present it to the KV
        # store (ref: secret.py make_secret_key; shipped via env like
        # the reference's _HOROVOD_SECRET_KEY plumbing).
        from .util import secret as secret_util

        server = RendezvousServer(secret_key=secret_util.make_secret_key())
    else:
        server = rendezvous
    port = server.start() if own_server else server.port
    addr = (
        "127.0.0.1"
        if all(is_local_host(s.hostname) for s in slots)
        else _driver_addr()
    )
    # HVDRUN_USE_TASK_SERVICE routes worker exec through per-slot
    # authenticated TaskServices instead of long-lived ssh sessions
    # ("1": remote slots only; "all": every slot — the no-ssh test
    # spelling). Requires the per-job secret, so only with own_server
    # or a keyed external server.
    ts_mode = os.environ.get("HVDRUN_USE_TASK_SERVICE", "")
    driver_service = None
    if ts_mode and server.secret_key is not None:
        ts_slots = [
            i for i, s in enumerate(slots)
            if ts_mode == "all" or not is_local_host(s.hostname)
        ]
    else:
        ts_slots = []
    # Everything from here shares one cleanup block: a failure while
    # spawning (ssh missing, task-service registration timeout, ...)
    # must tear down already-started workers, task-service bootstraps,
    # the DriverService, and the rendezvous server — not leak them.
    handles: List[WorkerHandle] = []
    exit_code = 0
    try:
        if ts_slots:
            driver_service, ts_handles = _spawn_via_task_service(
                [slots[i] for i in ts_slots], command,
                [slot_env(slots[i], addr, port, extra_env,
                          secret_key=server.secret_key) for i in ts_slots],
                server.secret_key, prefix_output, ssh_port,
                ssh_identity_file,
            )
        else:
            ts_handles = []
        ts_iter = iter(ts_handles)
        for i, slot in enumerate(slots):
            if i in ts_slots:
                handles.append(next(ts_iter))
            else:
                handles.append(spawn_worker(
                    slot, command,
                    slot_env(slot, addr, port, extra_env,
                             secret_key=server.secret_key),
                    verbose, prefix_output, ssh_port, ssh_identity_file,
                ))
        pending = set(range(len(handles)))
        while pending:
            for i in sorted(pending):
                rc = handles[i].poll()
                if rc is None:
                    continue
                pending.discard(i)
                if rc != 0:
                    exit_code = exit_code or rc
                    if verbose:
                        print(
                            f"hvdrun: rank {handles[i].slot.rank} exited "
                            f"with {rc}; terminating remaining workers",
                            file=sys.stderr,
                        )
                    terminate_workers([handles[j] for j in pending])
                    for j in list(pending):
                        pending.discard(j)
                    break
            else:
                import time

                time.sleep(0.05)
    except BaseException:
        # Spawn-time failure: stop whatever already started.
        terminate_workers(handles)
        raise
    finally:
        for h in handles:
            for t in h.threads:
                t.join(timeout=5)
        for h in handles:
            # Task-service bootstraps outlive their worker command;
            # shut them down explicitly.
            if isinstance(h, TaskServiceWorkerHandle):
                h.terminate()
        if driver_service is not None:
            driver_service.shutdown()
        if own_server:
            server.stop()
    return exit_code


def _spawn_via_task_service(
    slots: List[SlotInfo],
    command: Sequence[str],
    envs: List[Dict[str, str]],
    secret_key: bytes,
    prefix_output: bool,
    ssh_port: Optional[int],
    ssh_identity_file: Optional[str],
):
    """Bootstrap one TaskService per slot (ssh for remote hosts, plain
    subprocess for local ones), wait for their authenticated
    registrations at the DriverService, then start each worker command
    through TaskClient.run_command (ref: the reference's driver/task
    service launch flow, common/service/driver_service.py +
    task_service.py; ssh only bootstraps, exec rides the HMAC RPC)."""
    from .service import DriverClient, DriverService, TaskClient
    from .util import secret as secret_util

    driver_service = DriverService(num_tasks=len(slots), key=secret_key)
    driver_addr = (
        "127.0.0.1" if all(is_local_host(s.hostname) for s in slots)
        else _driver_addr()
    )
    boot_env = {env_cfg.SECRET_KEY: secret_util.key_to_env(secret_key)}
    boots = []
    for i, slot in enumerate(slots):
        boot_cmd = [
            sys.executable, "-m", "horovod_tpu.runner.task_runner",
            "--task-service", "--index", str(i),
            "--driver", f"{driver_addr}:{driver_service.port}",
        ]
        remote = not is_local_host(slot.hostname)
        if remote:
            # build_ssh_command strips the secret from the command line;
            # it is written to the ssh client's stdin below.
            argv = build_ssh_command(slot.hostname, boot_cmd, boot_env,
                                     ssh_port, ssh_identity_file)
            full_env = dict(os.environ)
        else:
            argv = boot_cmd
            full_env = dict(os.environ)
            full_env.update(boot_env)
        p = subprocess.Popen(
            argv, env=full_env, start_new_session=True,
            stdin=subprocess.PIPE if remote else None,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        if remote:
            try:
                p.stdin.write(
                    (boot_env[env_cfg.SECRET_KEY] + "\n").encode())
                p.stdin.flush()
            except (BrokenPipeError, OSError):
                pass
        boots.append(p)
    handles: List[WorkerHandle] = []
    try:
        addrs = driver_service.wait_for_all_tasks(timeout=120)
        for i, slot in enumerate(slots):
            host = ("127.0.0.1" if is_local_host(slot.hostname)
                    else slot.hostname)
            ts_port = next(iter(addrs[i].values()))
            client = TaskClient(host, ts_port, secret_key)
            client.run_command(list(command), env=envs[i])
            handles.append(TaskServiceWorkerHandle(
                slot, boots[i], client, prefix_output=prefix_output,
            ))
    except BaseException:
        # Registration timeout or a run_command failure: none of the
        # bootstraps may leak (they never exit on their own).
        for h in handles:
            h.terminate()
        for p in boots:
            p.kill()
        driver_service.shutdown()
        raise
    return driver_service, handles


def _driver_addr() -> str:
    # Workers must reach the driver's rendezvous server. For local-only
    # launches 127.0.0.1 works; for remote hosts use the routable name.
    return os.environ.get("HVDRUN_DRIVER_ADDR") or socket.gethostname()


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu distributed job "
        "(horovodrun equivalent)",
    )
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="total number of processes")
    p.add_argument("-H", "--hosts", default=None,
                   help='comma list "host1:slots,host2:slots"')
    p.add_argument("--hostfile", default=None,
                   help="mpirun-style hostfile")
    p.add_argument("--network-interface", default=None,
                   help="NIC to bind (informational; TCP mesh binds all)")
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--ssh-identity-file", default=None)
    p.add_argument("--verbose", "-v", action="store_true")
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print which frameworks and backends this "
                   "build supports, then exit "
                   "(ref: horovodrun --check-build)")
    p.add_argument("--disable-output-prefix", action="store_true",
                   help="don't prefix worker output with [rank]<>")
    # Elastic (ref: launch.py elastic flags)
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--slots-per-host", type=int, default=None)
    p.add_argument("--reset-limit", type=int, default=None)
    p.add_argument("--config-file", default=None,
                   help="YAML file of flag defaults "
                   "(ref: horovodrun --config-file, launch.py:212+)")
    config_parser.add_engine_args(p)
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command, e.g. python train.py")
    return p


def _apply_config_file(parser: argparse.ArgumentParser, args):
    """Fill unset args from a YAML config file: flat `dest: value`
    mapping, with nested sections flattened (`a: {b-c: 1}` → dest
    `b_c`), mirroring the reference's config-file layering where CLI
    flags win over file values (ref: launch.py:212+,
    runner/common/util/config_parser.py)."""
    import yaml

    with open(args.config_file) as f:
        data = yaml.safe_load(f) or {}
    flat = {}

    def walk(d):
        for k, v in d.items():
            if isinstance(v, dict):
                walk(v)
            else:
                flat[str(k).replace("-", "_")] = v

    walk(data)
    known = {a.dest for a in parser._actions}
    unknown = sorted(set(flat) - known)
    if unknown:
        raise SystemExit(
            f"hvdrun: unknown config-file keys: {', '.join(unknown)}"
        )
    for dest, val in flat.items():
        # Fill only values still at their parser default — an explicit
        # CLI `0` must not be clobbered (0 == False would match a
        # naive None/False sentinel check).
        if getattr(args, dest, None) == parser.get_default(dest):
            setattr(args, dest, val)


def check_build() -> str:
    """Render the framework/backend availability report
    (ref: horovod/runner/launch.py:106-141 check_build — the reference
    prints which extensions and collective backends were compiled in;
    here frameworks are importability probes and backends come from
    common.basics introspection)."""
    import importlib.util

    from .. import __version__
    from ..common import basics

    def chk(v) -> str:
        return "X" if v else " "

    def has(mod: str) -> bool:
        try:
            return importlib.util.find_spec(mod) is not None
        except (ImportError, ValueError):
            return False

    def native_built() -> bool:
        try:
            from ..cc import native

            return native.available()
        except Exception:
            return False

    return (
        f"Horovod-TPU v{__version__}:\n"
        "\n"
        "Available Frameworks:\n"
        f"    [{chk(has('jax'))}] JAX\n"
        f"    [{chk(has('tensorflow'))}] TensorFlow\n"
        f"    [{chk(has('torch'))}] PyTorch\n"
        f"    [{chk(has('mxnet'))}] MXNet\n"
        f"    [{chk(has('keras'))}] Keras\n"
        "\n"
        "Available Controllers:\n"
        f"    [{chk(basics.tcp_built())}] TCP (Gloo equivalent)\n"
        f"    [{chk(basics.mpi_built())}] MPI\n"
        "\n"
        "Available Tensor Operations:\n"
        f"    [{chk(basics.xla_built())}] XLA collectives (ICI/DCN)\n"
        f"    [{chk(basics.tcp_built())}] TCP star/ring/hier-ring\n"
        f"    [{chk(native_built())}] Native C++ reduction kernels\n"
        f"    [{chk(basics.nccl_built())}] NCCL\n"
        f"    [{chk(basics.ddl_built())}] DDL\n"
        f"    [{chk(basics.ccl_built())}] CCL\n"
    )


def run_commandline(argv: Optional[Sequence[str]] = None) -> int:
    parser = make_parser()
    args = parser.parse_args(argv)
    if args.check_build:
        print(check_build())
        return 0
    if args.config_file:
        _apply_config_file(parser, args)
    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2

    extra_env = config_parser.args_to_env(args)

    if args.host_discovery_script or (args.min_np is not None):
        from .elastic.launcher import launch_elastic

        return launch_elastic(args, command, extra_env)

    if args.hostfile:
        hosts = parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = parse_hosts(args.hosts)
    else:
        # No explicit hosts: auto-detect TPU-VM slice topology (one
        # worker process per pod host; SURVEY.md §5.8 — slice metadata
        # replaces the reference's ssh+NIC probing). Engage only when
        # the requested -np fits the slice (np unset, or one rank per
        # pod host); otherwise keep the historical local launch so
        # `hvdrun -np 4` on a pod worker still runs 4 local processes.
        hosts = discover_tpu_hosts()
        if hosts and args.num_proc not in (None, len(hosts)):
            hosts = None
        if hosts:
            if args.verbose:
                print(f"hvdrun: discovered TPU slice hosts: "
                      f"{','.join(h.hostname for h in hosts)}")
        else:
            np_ = args.num_proc or 1
            hosts = [HostInfo("localhost", np_)]
    np_ = args.num_proc or sum(h.slots for h in hosts)
    slots = get_host_assignments(hosts, np_, np_)
    if args.verbose:
        for s in slots:
            print(f"hvdrun: rank {s.rank} -> {s.hostname} "
                  f"(local {s.local_rank}/{s.local_size})")
    return launch_static(
        slots, command, extra_env, args.verbose,
        prefix_output=not args.disable_output_prefix,
        ssh_port=args.ssh_port, ssh_identity_file=args.ssh_identity_file,
    )


def main():  # console entry point
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
