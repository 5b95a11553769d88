"""Every cell's GSPMD step compiles for a TPU v5e at the cell's real
shape, without a chip: the guard each later PR gets at no chip time.

`jax.experimental.topologies` describes a v5e 2x2 to the installed
libtpu and XLA + Mosaic compile against it on this host. Nothing runs.
What it catches: a kernel Mosaic refuses, a step that no longer fits
the chip's memory, a cell that leaves the chip mostly empty, a dp=4
step without its all-reduce. One file and a module fixture, because
only one process at a time may load libtpu (on-chip-measurement guide,
section 2).
"""
import json
import math
import pathlib

import jax
import pytest

from benchmark import flops, harness
from benchmark.trainers import gspmd
from horovod_tpu.utils.compat import set_mesh

ROOT = pathlib.Path(__file__).resolve().parents[2]
INDEX = ROOT / "BENCHMARK.json"
HBM_LIMIT = 15.75 * 2 ** 30   # `bytes_limit` of a v5e chip's runtime


def _gspmd_cells():
    cells = []
    for entry in json.loads(INDEX.read_text())["workloads"]:
        traffic = json.loads((ROOT / "benchmark" / "traffic"
                              / f"{entry['traffic']}.json").read_text())
        if traffic["trainer"] == "gspmd":
            cells.append(entry["name"])
    return cells


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


@pytest.mark.parametrize("name", _gspmd_cells())
def test_cell_step_compiles_for_v5e_and_fills_the_chip(
        v5e_devices, no_compile_cache, name):
    cell = harness.load_cell(INDEX, name)
    phase = cell.phases[-1]
    n = math.prod(phase["mesh"].values())
    assert n == cell.chips
    trainer = gspmd.build(harness.make_model(cell), phase,
                          v5e_devices[:n], seed=0)
    state_sh, batch_sh = trainer.step.shardings
    ids = jax.ShapeDtypeStruct((trainer.global_batch, phase["seq"]),
                               "int32", sharding=batch_sh)
    with set_mesh(batch_sh.mesh):
        # trainer.init is make_train_step's init bound to its key; the
        # jitted function under it gives the state's shapes unexecuted.
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(trainer.init.func.__wrapped__, *trainer.init.args),
            state_sh)
        lowered = trainer.step.__wrapped__.lower(state, ids)
    flash = cell.dims["attn_impl"] == "flash"
    text = lowered.as_text()
    assert ("tpu_custom_call" in text) == flash
    assert ("flash_attention_fwd" in text
            and "flash_attention_bwd" in text) == flash
    compiled = lowered.compile()
    assert ("all-reduce" in compiled.as_text()) == (n > 1)
    memory = compiled.memory_analysis()
    used = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"{name}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB "
          f"+ temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB a chip")
    assert HBM_LIMIT / 2 < used < 15e9
    # XLA's own count of the step against the analytic functions: with the
    # flash kernel (a custom call it cannot see into) the matmuls alone,
    # with dense attention the whole model; XLA adds the elementwise ops.
    tokens = phase["batch_per_chip"] * phase["seq"]
    counted = compiled.cost_analysis()["flops"] / tokens
    analytic = (6 * flops.transformer_matmul_params(cell.dims) if flash
                else flops.transformer(cell.dims, phase["seq"]))
    print(f"{name}: XLA counts {counted / 1e6:.1f} MFLOP a token, "
          f"analytic {analytic / 1e6:.1f}")
    assert analytic < counted < 1.02 * analytic
