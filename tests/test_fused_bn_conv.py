"""Fused BN-apply + ReLU + 1x1-conv + stats kernel vs the unfused
composition (interpret mode on CPU; the real win is measured on TPU —
see docs/kernels.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.fused_bn_conv import (
    _reference_bn_relu_matmul,
    bn_relu_conv1x1,
    fused_bn_relu_matmul,
)


def _inputs(m=1024, cin=256, cout=128, seed=0, dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, cin), dtype)
    mu = jnp.asarray(rng.randn(cin), jnp.float32) * 0.1
    var = jnp.asarray(rng.rand(cin) + 0.5, jnp.float32)
    gamma = jnp.asarray(rng.rand(cin) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(cin) * 0.1, jnp.float32)
    w = jnp.asarray(rng.randn(cin, cout) / np.sqrt(cin), dtype)
    return x, mu, var, gamma, beta, w


@pytest.mark.parametrize("accum", ["scratch", "revisit"])
def test_fused_matches_reference(accum):
    args = _inputs()
    y, s1, s2 = fused_bn_relu_matmul(*args, interpret=True, accum=accum)
    yr, s1r, s2r = _reference_bn_relu_matmul(*args, 1e-5)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(s1, s1r, rtol=2e-2, atol=2.0)
    np.testing.assert_allclose(s2, s2r, rtol=3e-2, atol=3.0)


@pytest.mark.parametrize("accum", ["scratch", "revisit"])
def test_fused_multiblock_stats_accumulate(accum):
    """M spans several grid blocks: the epilogue must accumulate stats
    across blocks, not overwrite them — in both grid layouts."""
    args = _inputs(m=2048, cin=128, cout=256)
    y, s1, s2 = fused_bn_relu_matmul(*args, interpret=True, block_m=512,
                                     accum=accum)
    _, s1r, s2r = _reference_bn_relu_matmul(*args, 1e-5)
    np.testing.assert_allclose(s1, s1r, rtol=2e-2, atol=4.0)
    np.testing.assert_allclose(s2, s2r, rtol=3e-2, atol=6.0)


def test_custom_vjp_matches_reference_grads():
    args = _inputs(m=512, cin=128, cout=128, dtype=jnp.float32)

    def loss_fused(x, gamma, beta, w):
        y, s1, s2 = bn_relu_conv1x1(x, args[1], args[2], gamma, beta, w)
        return (jnp.sum(y.astype(jnp.float32) ** 2) * 1e-3
                + jnp.sum(s1) * 1e-3 + jnp.sum(s2) * 1e-4)

    def loss_ref(x, gamma, beta, w):
        y, s1, s2 = _reference_bn_relu_matmul(
            x, args[1], args[2], gamma, beta, w, 1e-5)
        return (jnp.sum(y.astype(jnp.float32) ** 2) * 1e-3
                + jnp.sum(s1) * 1e-3 + jnp.sum(s2) * 1e-4)

    x, _, _, gamma, beta, w = args
    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, gamma, beta, w)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, gamma, beta, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_block_divisibility_error():
    args = _inputs(m=1000)  # not divisible by 512
    with pytest.raises(ValueError, match="divisible"):
        fused_bn_relu_matmul(*args, interpret=True)


@pytest.mark.parametrize("shape", [(2, 4, 4, 64), (3, 16, 16, 64)])
def test_fused_module_matches_unfused_composition(shape):
    """FusedBNReluConv1x1 (the model-wired form) == BatchNorm(train) →
    ReLU → 1x1 conv with the same parameters, running stats update
    included. The second shape has M=768 — above the 512 block but not
    a multiple of it — exercising the module's pad-and-slice path."""
    from horovod_tpu.models.resnet import FusedBNReluConv1x1

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    mod = FusedBNReluConv1x1(128, dtype=jnp.float32)
    variables = mod.init(jax.random.PRNGKey(0), x, train=True)
    y, updates = mod.apply(x=x, train=True, mutable=["batch_stats"],
                           variables=variables)

    p = variables["params"]
    x2d = np.asarray(x.reshape(-1, 64), np.float64)
    mu = x2d.mean(0)
    var = x2d.var(0)
    ref = np.maximum(
        (x2d - mu) / np.sqrt(var + 1e-5) * np.asarray(p["scale"])
        + np.asarray(p["bias"]), 0.0
    ) @ np.asarray(p["kernel"], np.float64)
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, 128), ref, rtol=2e-4, atol=2e-4)
    # Running stats moved toward the batch stats (momentum 0.9).
    np.testing.assert_allclose(
        np.asarray(updates["batch_stats"]["mean"]), 0.1 * mu, rtol=1e-3,
        atol=1e-5)


def test_resnet50_fused_stage_trains():
    """resnet50 with fuse_bn_conv_stages=(1,) runs a full train step
    (interpret-mode kernel on CPU) with a finite decreasing loss."""
    import optax

    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.train import make_train_step, softmax_xent

    import jax as _jax

    spec = get_model("resnet50")
    model = spec.make_model(num_classes=10, fuse_bn_conv_stages=(1,))
    rng = np.random.RandomState(0)
    n = len(_jax.devices())
    images = rng.rand(n, 32, 32, 3).astype(np.float32)
    labels = rng.randint(0, 10, size=(n,), dtype=np.int32)
    mesh = create_mesh({"dp": n})
    build = make_train_step(model, optax.sgd(0.1, momentum=0.9),
                            softmax_xent, mesh=mesh,
                            has_batch_stats=True)
    init_fn, step_fn, _ = build(jax.random.PRNGKey(0), images, labels)
    state = init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(3):
        state, loss = step_fn(state, images, labels)
        losses.append(float(loss))
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], losses


def test_block_n_not_a_lane_multiple_is_rejected_up_front():
    """Cout=64 → block_n=64: Mosaic refuses the kernel's lane slices on
    a chip, which interpret mode never shows, so it is a ValueError
    before any platform is involved."""
    args = _inputs(cout=64)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        fused_bn_relu_matmul(*args, interpret=True)
