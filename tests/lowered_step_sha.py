#!/usr/bin/env python3
"""Is a cell's program the parent's? sha256 of every cell's train step
lowered for a described TPU v5e, without a chip.

    python tests/lowered_step_sha.py <tree> [<cell> ...]

prints `<sha256>  <cell>` for each phase of each cell of `<tree>`'s
BENCHMARK.json (all of them, or the named ones), importing `benchmark`
and `horovod_tpu` from `<tree>`. To compare two commits, unpack each
with `git archive` AT THE SAME PATH, one after the other, run this file
on both and diff the outputs: Mosaic's serialised kernel carries the
file and line of its whole call stack, so a tree at another path, or a
comment that adds a line to a module a cell imports, is another text
with equal code. About 2.5 minutes for the five cells.

Not a test: one process at a time may load libtpu, which
tests/benchmarking/test_benchmark_cells_compile_for_v5e.py does in
tier 1, and a hash says nothing without the other tree's.
"""
import hashlib
import importlib
import json
import math
import os
import pathlib
import sys

TREE = pathlib.Path(sys.argv[1]).resolve()
sys.path.insert(0, str(TREE))
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from benchmark import harness  # noqa: E402

assert pathlib.Path(hvd.__file__).is_relative_to(TREE), hvd.__file__


class _Lowered(Exception):
    pass


def lower_first_call(trainer, model, phase, devices):
    """The step of a trainer without a `lower` (hvd: `wrap_step` builds
    its program on the first call): the trainer as `build` makes it,
    called on shapes, with the `jax.jit` inside asked to lower."""
    built = trainer.build(model, phase, devices, seed=0)
    mesh = hvd.mesh()
    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated),
        jax.eval_shape(built.init))
    ids = jax.ShapeDtypeStruct(
        (built.global_batch, phase["seq"]), "int32",
        sharding=NamedSharding(mesh, P(hvd.axis_name())))
    jit = jax.jit

    def lowering_jit(f, **kw):
        def call(*args):
            raise _Lowered(jit(f, **kw).lower(*args))
        return call

    jax.jit = lowering_jit
    try:
        built.step(state, ids)
    except _Lowered as stop:
        return stop.args[0]
    finally:
        jax.jit = jit
        built.close()
    raise AssertionError("the trainer's first call reached no jax.jit")


def main():
    index = TREE / "BENCHMARK.json"
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    names = sys.argv[2:] or [
        entry["name"] for entry in json.loads(index.read_text())["workloads"]]
    for name in names:
        cell = harness.load_cell(index, name)
        trainer = importlib.import_module(
            f"benchmark.trainers.{cell.traffic['trainer']}")
        for phase in cell.phases:
            n = math.prod(phase["mesh"].values())
            lower = getattr(trainer, "lower", None)
            lowered = (
                lower(harness.make_model(cell), phase, devices[:n]) if lower
                else lower_first_call(trainer, harness.make_model(cell),
                                      phase, devices[:n]))
            text = lowered.as_text()
            tag = name if len(cell.phases) == 1 else f"{name} {phase['mesh']}"
            print(f"{hashlib.sha256(text.encode()).hexdigest()}  {tag}",
                  flush=True)


if __name__ == "__main__":
    main()
