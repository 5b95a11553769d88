"""Every cell whose trainer can lower its step from shapes alone (a
module-level `lower`, benchmark/trainers/__init__.py) compiles for a TPU
v5e at the cell's real shape, without a chip: the guard each later PR
gets at no chip time. What a cell is held to is its own: the kernels its
configuration expects in the lowered step and the matmul count of the
function the configuration names.

`jax.experimental.topologies` describes a v5e 2x2 to the installed
libtpu and XLA + Mosaic compile against it on this host. Nothing runs.
What it catches: a kernel Mosaic refuses, a step that no longer fits
the chip's memory, a cell that leaves the chip mostly empty, a dp=4
step without its all-reduce. One file and a module fixture, because
only one process at a time may load libtpu (on-chip-measurement guide,
section 2).
"""
import importlib
import json
import math
import pathlib

import jax
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
INDEX = ROOT / "BENCHMARK.json"
HBM_LIMIT = 15.75 * 2 ** 30   # `bytes_limit` of a v5e chip's runtime


def _trainer(cell):
    return importlib.import_module(
        f"benchmark.trainers.{cell.traffic['trainer']}")


def _lowerable_cells():
    names = [entry["name"]
             for entry in json.loads(INDEX.read_text())["workloads"]]
    return [name for name in names
            if hasattr(_trainer(harness.load_cell(INDEX, name)), "lower")]


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or it knows no v5e
        pytest.skip(f"no v5e:2x2 topology to compile against: {exc}")
    return topo.devices


@pytest.fixture(scope="module")
def no_compile_cache():
    """A deviceless executable can be written to the persistent cache
    but not read back; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", _lowerable_cells())
def test_cell_step_compiles_for_v5e_and_fills_the_chip(
        v5e_devices, no_compile_cache, name):
    cell = harness.load_cell(INDEX, name)
    phase = cell.phases[-1]
    n = math.prod(phase["mesh"].values())
    assert n == cell.chips
    lowered = _trainer(cell).lower(harness.make_model(cell), phase,
                                   v5e_devices[:n])
    kernels = cell.config["kernels"]
    text = lowered.as_text()
    assert ("tpu_custom_call" in text) == bool(kernels)
    assert [k for k in kernels if k not in text] == []
    compiled = lowered.compile()
    assert ("all-reduce" in compiled.as_text()) == (n > 1)
    memory = compiled.memory_analysis()
    used = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"{name}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB "
          f"+ temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB a chip")
    assert HBM_LIMIT / 2 < used < 15e9
    # XLA's own count of the step against the configuration's analytic
    # functions: with kernels (custom calls it cannot see into) the
    # matmuls alone, forward and two gradients each; without, the whole
    # model. XLA adds the elementwise ops.
    tokens = phase["batch_per_chip"] * phase["seq"]
    counted = compiled.cost_analysis()["flops"] / tokens
    analytic = (
        6 * harness.named(cell.config["matmul_params"])(cell.dims) if kernels
        else harness.named(cell.config["flops_per_token"])(cell.dims,
                                                           phase["seq"]))
    print(f"{name}: XLA counts {counted / 1e6:.1f} MFLOP a token, "
          f"analytic {analytic / 1e6:.1f}")
    assert analytic < counted < 1.02 * analytic
