"""Compile for a TPU v5e without one.

`jax.experimental.topologies` describes a v5e 2x2 to the installed
libtpu, and XLA + Mosaic compile against it on this CPU host. Nothing
runs — ownership of the chip, placement, numerics and HBM at run time
are `chip_smoke.py`'s business — but this is how a session without the
chip learns that Mosaic refuses a kernel, which interpret mode never
shows. Slow (the train steps take about half a minute each to compile).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import get_model
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.mesh import create_mesh
from horovod_tpu.parallel.train import lm_loss, make_train_step
from horovod_tpu.utils.compat import set_mesh

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or it knows no v5e
        pytest.skip(f"no v5e:2x2 topology to compile against: {exc}")
    return topo.devices


@pytest.mark.parametrize("S,causal,padded", [
    (2048, True, False),   # 4 x 4 tiles of 512
    (4096, True, False),   # 8 x 8 tiles of 512
    (1000, False, True),   # q and k padded to the block, padding mask
])
def test_flash_forward_backward_compiles_for_v5e(v5e_devices, S, causal,
                                                 padded):
    B, H, D = 2, 4, 64
    sh = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("d",)), P())
    qkv = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=sh)
    mask = (jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=sh)
            if padded else None)

    def loss(q, k, v, mask):
        out = flash_attention(q, k, v, mask, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, mask)
    # Lowered for the TPU from a CPU host: the interpret rule follows
    # the platform the call is built for, not this process's backend.
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    lowered.compile()


@pytest.mark.parametrize("n", [1, 4])
def test_gpt2_small_train_step_compiles_for_v5e(v5e_devices, n):
    """The step chip_smoke.py runs: gpt2-small as
    the registry publishes it, seq 2048, batch 4 per chip, flash, bf16
    logits, adamw — on one device and on a dp=4 mesh."""
    seq, batch = 2048, 4 * n
    mesh = create_mesh({"dp": n}, devices=v5e_devices[:n])
    spec = get_model("gpt2-small")
    model = spec.make_model(attn_impl="flash", max_len=seq,
                            logits_dtype=jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    build = make_train_step(model, optax.adamw(1e-4), lm_loss, mesh=mesh)
    rng = jax.random.PRNGKey(0)  # made here: nothing may execute below
    init_fn, step_fn, state_sh = build(rng, ids)
    with set_mesh(mesh):
        state = jax.eval_shape(init_fn.__wrapped__, rng)
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state, state_sh)
        lowered = step_fn.__wrapped__.lower(
            state, jax.ShapeDtypeStruct(ids.shape, ids.dtype,
                                        sharding=step_fn.shardings[1]))
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    if n > 1:
        assert "all-reduce" in text
    # The data-parallel options (make_train_step sets them: the described
    # devices are TPU chips) are taken by this libtpu and change the
    # schedule: asynchronous, fused reduces at dp=4, none on one chip.
    assert ("async-collective-start" in text) == (n > 1)
