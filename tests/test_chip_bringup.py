"""What a CPU can check of the path to the chip: where the compile
cache goes, that the launcher's parent never opens a backend, that
local workers are each given their own chip, that Pallas kernels are
interpreted only where they are lowered for the CPU, and that the
programs which must fail off the chip do."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.pallas_platform import call_by_platform
from horovod_tpu.runner import launch
from horovod_tpu.runner.hosts import HostInfo, get_host_assignments
from horovod_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, env: dict, cwd: str = REPO) -> str:
    env = {**env, "PYTHONPATH": REPO}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# --- compile cache ---------------------------------------------------------

def test_cache_dir_from_environment_sets_nothing_in_code(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert updates == []  # jax reads the variable itself
    assert os.environ[compile_cache.ENV_VAR] == "/some/dir"


def test_cache_dir_default_is_one_path_in_the_checkout(tmp_path):
    """Unset → the same in-checkout path from two separate processes
    (whatever their working directory), exported for launched workers,
    and known to a jax that was imported before the call."""
    code = (
        "import os, jax\n"
        "from horovod_tpu.utils.compile_cache import enable_compile_cache\n"
        "p = enable_compile_cache()\n"
        "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == p\n"
        "assert jax.config.jax_compilation_cache_dir == p\n"
        "print(p)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    first = _python(code, env).strip()
    second = _python(code, env, cwd=str(tmp_path)).strip()
    assert first == second == os.path.join(REPO, ".jax_cache")


# --- the launcher's parent -------------------------------------------------

def test_launcher_parent_initialises_no_backend():
    """Host discovery and a whole static launch, in a fresh process:
    jax may be imported (the package imports it) but no backend may
    exist afterwards — on a chip machine the parent would hold the chip
    its workers need."""
    code = (
        "import sys\n"
        "from horovod_tpu.runner.hosts import discover_tpu_hosts\n"
        "from horovod_tpu.runner.launch import run_commandline\n"
        "assert discover_tpu_hosts() is None\n"
        "rc = run_commandline(['-np', '1', sys.executable, '-c', 'pass'])\n"
        "assert rc == 0, rc\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "TPU_WORKER_HOSTNAMES"}
    _python(code, env)


# --- one process for each chip ---------------------------------------------

def _local_slots(n):
    return get_host_assignments([HostInfo("localhost", n)], n, n)


def _envs(n, extra_env=None):
    return [launch.slot_env(s, "127.0.0.1", 1234, extra_env)
            for s in _local_slots(n)]


VISIBILITY = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS")


def test_local_workers_get_distinct_chips(monkeypatch):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    for platforms in ("tpu", "tpu,cpu", ""):  # "" = unset: jax picks TPU
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        envs = _envs(4)
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
                   and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


def test_no_chip_pinning_for_cpu_workers_or_a_single_worker(monkeypatch):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not any(k in e for e in _envs(4) for k in VISIBILITY)
    # The caller's extra_env decides over the inherited platform.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    envs = _envs(4, {"JAX_PLATFORMS": "cpu"})
    assert not any(k in e for e in envs for k in VISIBILITY)
    # One worker owns every chip of the host: nothing to divide.
    assert not any(k in e for e in _envs(1) for k in VISIBILITY)
    # A host without TPU chips: nothing to pin, nothing to refuse.
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    assert not any(k in e for e in _envs(4) for k in VISIBILITY)


def test_more_local_workers_than_chips_is_an_error(monkeypatch):
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 2)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match=r"4 workers .* only 2 TPU chip"):
        _envs(4)


# --- the interpret rule ----------------------------------------------------

def test_interpret_only_where_lowered_for_cpu():
    """cpu → the interpreter branch, anything else → the compiled one,
    chosen by the platform the call is lowered for and not by this
    (CPU) process's default backend."""
    fn = jax.jit(lambda x: call_by_platform(
        lambda interpret: jnp.tanh if interpret else jnp.exp, x))
    x = jnp.ones((8,), jnp.float32)
    for platform, interpreted in (("cpu", True), ("tpu", False),
                                  ("cuda", False)):
        text = fn.trace(x).lower(lowering_platforms=(platform,)).as_text()
        assert ("stablehlo.tanh" in text) == interpreted, platform
        assert ("stablehlo.exponential" in text) != interpreted, platform
    # An explicit mode holds on every platform.
    forced = jax.jit(lambda x: call_by_platform(
        lambda interpret: jnp.tanh if interpret else jnp.exp, x,
        interpret=True))
    assert "stablehlo.tanh" in forced.trace(x).lower(
        lowering_platforms=("tpu",)).as_text()


def test_interpret_rule_swallows_nothing():
    def make_call(interpret):
        if not interpret:
            raise RuntimeError("the compiled path broke")
        return jnp.tanh

    with pytest.raises(RuntimeError, match="compiled path broke"):
        call_by_platform(make_call, jnp.ones((8,)))


def test_flash_attention_lowers_to_mosaic_for_tpu_only():
    q = jnp.ones((1, 256, 2, 64), jnp.bfloat16)
    traced = jax.jit(
        lambda q, k, v: flash_attention(q, k, v)).trace(q, q, q)
    assert "tpu_custom_call" in traced.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()


# --- programs that must fail off the chip ----------------------------------

def test_chip_smoke_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout == ""  # no result line


def test_chip_smoke_result_line_has_exactly_the_contract_keys(
        monkeypatch, capsys):
    """The driver parses the last stdout line: "ok" and "device"
    (platform, kind, count) and nothing else. Everything informational
    goes on the line before it."""
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    fake = {
        "kernel": {"causal_s2048": {"out": 0.004},
                   "phase_seconds": 1.0},
        "train": {"device": device, "versions": {"jax": "0.9.0"},
                  "cache_dir": "/x/.jax_cache", "compile_seconds": {},
                  "step_ms": {}, "memory_gib": {}, "losses": {},
                  "phase_seconds": 2.0},
        "launcher": {"np": 4, "seconds": 3.0, "phase_seconds": 3.0},
    }
    asked = []

    def run_phase(name, extra, seconds):
        asked.append((name, extra))
        return fake[name]

    monkeypatch.setattr(chip_smoke, "run_phase", run_phase)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert chip_smoke.parent() == 0
    assert asked[-1] == ("launcher", ["--chips", "4"])
    lines = capsys.readouterr().out.splitlines()
    result = json.loads(lines[-1])
    assert result == {"ok": True, "device": device}
    assert set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert isinstance(result["device"]["count"], int)
    report = json.loads(lines[-2].removeprefix("report: "))
    assert report["claim"] is None and "versions" in report


def test_chip_smoke_unknown_device_kind_is_an_error(monkeypatch, capsys):
    """`require_tpu` knows the device kinds of benchmark/peaks.py and
    no others, and says where a new kind is added."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    class Device:
        platform = "tpu"
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Device()])
    with pytest.raises(chip_smoke.SmokeFailure,
                       match=r"'TPU v99' is not in benchmark/peaks\.py"):
        chip_smoke.require_tpu()
    Device.device_kind = "TPU v5 lite"
    assert chip_smoke.require_tpu()[0].device_kind == "TPU v5 lite"
    assert "ok: a TPU of kind 'TPU v5 lite'" in capsys.readouterr().out
    Device.platform = "cpu"
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.require_tpu()


@pytest.mark.slow
def test_chip_smoke_train_phase_logic_at_tiny_size():
    """The smoke's train phase — both spellings of the trainer and the
    several-device assertions — on the 8 virtual CPU devices at a tiny
    width, so its logic is debugged here and chip minutes go to the
    chip's own questions."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    import horovod_tpu as hvd

    hvd.shutdown()
    result = chip_smoke.train_phase(
        seq=128, batch_per_chip=2, steps=3, on_tpu=False,
        model_kw=dict(n_layers=2, d_model=128, n_heads=2, d_ff=256))
    assert result["device"]["count"] == len(jax.devices())
    assert set(result["losses"]) == {"make_train_step", "wrap_step"}
