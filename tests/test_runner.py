"""Launcher tests (ref test model: test/test_run.py — arg parsing, exact
command/env construction golden tests, host parsing; plus live local
integration the way test/integration/test_static_run.py runs real jobs)."""
import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner.config_parser import args_to_env
from horovod_tpu.runner.hosts import (
    HostInfo,
    get_host_assignments,
    parse_hostfile,
    parse_hosts,
)
from horovod_tpu.runner.launch import (
    build_ssh_command,
    launch_static,
    make_parser,
    slot_env,
)


def test_parse_hosts():
    hosts = parse_hosts("h1:2,h2:4,h3")
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("h1", 2), ("h2", 4), ("h3", 1)
    ]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("h1 slots=2\n# comment\nh2:3\nh4\n")
    hosts = parse_hostfile(str(f))
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("h1", 2), ("h2", 3), ("h4", 1)
    ]


def test_host_assignments_topology():
    """(ref: hosts.py:106-155 rank packing)"""
    slots = get_host_assignments([HostInfo("a", 2), HostInfo("b", 2)], 4)
    got = [
        (s.rank, s.hostname, s.local_rank, s.cross_rank, s.local_size,
         s.cross_size)
        for s in slots
    ]
    assert got == [
        (0, "a", 0, 0, 2, 2),
        (1, "a", 1, 0, 2, 2),
        (2, "b", 0, 1, 2, 2),
        (3, "b", 1, 1, 2, 2),
    ]
    assert all(s.size == 4 for s in slots)


def test_host_assignments_max_np_truncates():
    slots = get_host_assignments([HostInfo("a", 4), HostInfo("b", 4)], 2, 3)
    assert len(slots) == 3
    assert [s.hostname for s in slots] == ["a", "a", "a"]


def test_host_assignments_insufficient_slots():
    with pytest.raises(ValueError, match="only 2 slots"):
        get_host_assignments([HostInfo("a", 2)], 4)


def test_slot_env_golden():
    """Exact worker env contract (ref: gloo_run.py:65-198)."""
    slots = get_host_assignments([HostInfo("localhost", 2)], 2)
    env = slot_env(slots[1], "127.0.0.1", 9999)
    assert env == {
        "HOROVOD_RANK": "1",
        "HOROVOD_SIZE": "2",
        "HOROVOD_LOCAL_RANK": "1",
        "HOROVOD_LOCAL_SIZE": "2",
        "HOROVOD_CROSS_RANK": "0",
        "HOROVOD_CROSS_SIZE": "1",
        "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
        "HOROVOD_GLOO_RENDEZVOUS_PORT": "9999",
        "HOROVOD_HOSTNAME": "localhost",
        "HOROVOD_CONTROLLER": "tcp",
        "HOROVOD_CPU_OPERATIONS": "tcp",
    }


def test_ssh_command_golden():
    cmd = build_ssh_command(
        "worker1", ["python", "train.py"], {"HOROVOD_RANK": "3"},
        ssh_port=2222,
    )
    assert cmd[:5] == ["ssh", "-o", "StrictHostKeyChecking=no", "-p", "2222"]
    assert cmd[5] == "worker1"
    assert "HOROVOD_RANK=3" in cmd[6]
    assert "python train.py" in cmd[6]


def test_args_to_env_mapping():
    """(ref: config_parser.py set_env_from_args)"""
    args = make_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "2.5",
         "--cache-capacity", "512", "--timeline-filename", "/tmp/t.json",
         "--log-level", "DEBUG", "--no-stall-check", "--", "python", "x.py"]
    )
    env = args_to_env(args)
    assert env == {
        "HOROVOD_FUSION_THRESHOLD": str(32 * 1024 * 1024),
        "HOROVOD_CYCLE_TIME": "2.5",
        "HOROVOD_CACHE_CAPACITY": "512",
        "HOROVOD_TIMELINE": "/tmp/t.json",
        "HOROVOD_LOG_LEVEL": "DEBUG",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
    }


def test_parser_command_remainder():
    args = make_parser().parse_args(["-np", "4", "python", "train.py", "--lr",
                                     "0.1"])
    assert args.num_proc == 4
    assert args.command == ["python", "train.py", "--lr", "0.1"]


# ---------------------------------------------------------------------------
_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    hvd.init()
    out = hvd.allreduce(np.ones(3, np.float32) * (hvd.rank() + 1),
                        average=False)
    assert out.tolist() == [3.0, 3.0, 3.0], out
    print(f"worker rank {hvd.rank()} done")
    """
)


def test_launch_static_two_local_workers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    slots = get_host_assignments([HostInfo("localhost", 2)], 2)
    rc = launch_static(
        slots, [sys.executable, str(script)],
        extra_env={"PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
                   "HOROVOD_CYCLE_TIME": "1"},
    )
    assert rc == 0


def test_launch_static_propagates_failure(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text("import sys; sys.exit(3)")
    slots = get_host_assignments([HostInfo("localhost", 2)], 2)
    rc = launch_static(slots, [sys.executable, str(script)])
    assert rc == 3


def test_run_func_mode():
    from horovod_tpu.runner import run

    def fn():
        import horovod_tpu as hvd

        hvd.init()
        return hvd.rank() * 10

    results = run(fn, np=2, extra_env={"HOROVOD_CYCLE_TIME": "1"})
    assert results == [0, 10]


def test_hvdrun_cli_end_to_end(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    env["HOROVOD_CYCLE_TIME"] = "1"
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "[0]<stdout>:" in out.stdout and "[1]<stdout>:" in out.stdout


def test_config_file_yaml(tmp_path):
    """YAML config fills unset flags; CLI wins; unknown keys rejected
    (ref: horovodrun --config-file, launch.py:212+)."""
    from horovod_tpu.runner.launch import make_parser, _apply_config_file

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "num-proc: 4\ntuning:\n  fusion-threshold-mb: 8\n  cycle-time-ms: 2\n"
    )
    parser = make_parser()
    args = parser.parse_args(
        ["--config-file", str(cfg), "--cycle-time-ms", "9", "x"]
    )
    _apply_config_file(parser, args)
    assert args.num_proc == 4
    assert args.fusion_threshold_mb == 8
    assert args.cycle_time_ms == 9  # CLI beats file

    bad = tmp_path / "bad.yaml"
    bad.write_text("not-a-flag: 1\n")
    args2 = parser.parse_args(["--config-file", str(bad), "x"])
    try:
        _apply_config_file(parser, args2)
        assert False, "unknown key accepted"
    except SystemExit as e:
        assert "not_a_flag" in str(e)


def test_discover_tpu_hosts_env(monkeypatch):
    """TPU-VM slice metadata drives host discovery (SURVEY.md §5.8)."""
    from horovod_tpu.runner.hosts import discover_tpu_hosts

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-a,tpu-b,tpu-c")
    hosts = discover_tpu_hosts()
    assert [h.hostname for h in hosts] == ["tpu-a", "tpu-b", "tpu-c"]
    assert all(h.slots == 1 for h in hosts)

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "solo")
    assert discover_tpu_hosts() is None  # single host -> not a pod


def test_ssh_command_keeps_secret_off_cmdline():
    """The per-job HMAC key must ride ssh stdin, never the command line
    (visible in /proc/*/cmdline otherwise)."""
    from horovod_tpu.runner.launch import build_ssh_command
    from horovod_tpu.utils import env as env_cfg

    env = {"HOROVOD_RANK": "3", env_cfg.SECRET_KEY: "deadbeef" * 8}
    argv = build_ssh_command("hostA", ["python", "train.py"], env)
    joined = " ".join(argv)
    assert "deadbeef" not in joined
    assert "HOROVOD_RANK=3" in joined
    # The remote command reads the key from stdin instead.
    assert f"IFS= read -r {env_cfg.SECRET_KEY}" in joined
    assert f"export {env_cfg.SECRET_KEY}" in joined

    # Without a secret, no stdin plumbing is injected.
    argv2 = build_ssh_command("hostA", ["python", "train.py"],
                              {"HOROVOD_RANK": "3"})
    assert "read -r" not in " ".join(argv2)


def test_check_build_golden():
    """hvdrun --check-build prints the availability report and exits 0
    (ref: horovod/runner/launch.py:106-149,225 — horovodrun -cb)."""
    from horovod_tpu.runner.launch import check_build, run_commandline

    out = check_build()
    # Structure: three sections, reference-style checkbox rows.
    for section in ("Available Frameworks:", "Available Controllers:",
                    "Available Tensor Operations:"):
        assert section in out, out
    # This build always ships the JAX/XLA path and the TCP controller.
    assert "[X] JAX" in out
    assert "[X] TCP (Gloo equivalent)" in out
    assert "[X] XLA collectives (ICI/DCN)" in out
    # Backends that do not exist by design are reported absent.
    assert "[ ] NCCL" in out
    assert "[ ] DDL" in out
    assert "[ ] CCL" in out
    assert "[ ] MPI" in out
    # CLI: --check-build works without -np or a command.
    assert run_commandline(["--check-build"]) == 0
