"""Pallas flash-attention kernel tests (interpret mode on CPU; the same
kernel compiles via Mosaic on TPU — validated on hardware, see
ops/flash_attention.py docstring).

Reference oracle: parallel/ring.py dense_attention (itself verified
against the ring/ulysses SP kernels in test_parallel.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.utils.compat import set_mesh as _set_mesh
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring import dense_attention


def _qkv(B=2, S=96, H=2, D=32, dtype=np.float32, seed=0, Dv=None):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, S, H, d).astype(np.float32), dtype)
        for d in (D, D, Dv or D)
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [64, 96, 130])  # incl. non-multiple-of-block
def test_flash_matches_dense(causal, S):
    q, k, v = _qkv(S=S)
    got = flash_attention(q, k, v, causal=causal, block_q=64,
                          interpret=True)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_padding_mask(causal):
    q, k, v = _qkv(S=96)
    mask = np.ones((2, 96), np.float32)
    mask[0, 60:] = 0.0
    mask[1, 10:] = 0.0
    got = flash_attention(q, k, v, jnp.asarray(mask), causal=causal,
                          block_q=64, interpret=True)
    want = dense_attention(q, k, v, causal=causal, mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_flash_fully_masked_rows_zero():
    """An all-padding sequence yields zeros (BERT convention, matching
    the other kernels)."""
    q, k, v = _qkv(S=64)
    mask = np.ones((2, 64), np.float32)
    mask[1, :] = 0.0
    got = flash_attention(q, k, v, jnp.asarray(mask), causal=False,
                          block_q=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[1], 0.0)
    assert np.isfinite(np.asarray(got)).all()


def _assert_grads_match(q, k, v, jmask, causal, block_q, tol=2e-4):
    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, jmask, causal=causal,
                                       block_q=block_q,
                                       interpret=True
                                       ).astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal,
                                       mask=jmask) ** 2)

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*f32)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("S", [64, 96, 130])  # incl. q-padding paths
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("use_mask", [False, True])
def test_flash_gradients_match_dense(S, causal, use_mask):
    q, k, v = _qkv(S=S)
    if use_mask:
        mask = np.ones((2, S), np.float32)
        mask[0, S - 10:] = 0.0
        mask[1, S // 3:] = 0.0
        jmask = jnp.asarray(mask)
    else:
        jmask = None
    _assert_grads_match(q, k, v, jmask, causal, block_q=64)


@pytest.mark.parametrize("S", [1024, 1025])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("use_mask", [False, True])
def test_flash_long_sequence_interior_tiles(S, causal, use_mask):
    """S > block_k (512): the kernels stream MULTIPLE k-tiles — a scale
    short-S tests (bk=min(512,S)=S → one tile) can never reach.

    S=1024 (512-multiple, pad_k=0): with no mask the below-diagonal
    tiles take the mask-free `plain` body, the only CI coverage of that
    path; with a mask, the multi-tile MASKED path at the same scale.
    S=1025: keys pad to 1536 with an (almost) fully-masked final
    k-tile, so `plain` is forced off even with mask=None and the
    synthesized all-ones-then-padded mask path runs multi-tile. Covers
    fwd and the fused single-sweep backward (interior/diagonal loop
    splits in both)."""
    q, k, v = _qkv(B=1, S=S, H=2, D=16)
    if use_mask:
        mask = np.ones((1, S), np.float32)
        mask[0, 900:] = 0.0
        jmask = jnp.asarray(mask)
    else:
        jmask = None
    got = flash_attention(q, k, v, jmask, causal=causal, block_q=128,
                          interpret=True)
    want = dense_attention(q, k, v, causal=causal, mask=jmask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    _assert_grads_match(q, k, v, jmask, causal, block_q=128)


# One case for every branch of the key-major bodies at the geometry
# `_blocks` picks itself (block_q=None), which the short tests above
# never reach: S, Dqk, Dv, dtype, causal, padding mask, block_q.
_GEOMETRY_CASES = {
    # D 64: the scale is 1/8 and folds into the operand block.
    "d64-causal-one-tile": (512, 64, 64, np.float32, True, False, None),
    "d64-causal-2x2-tiles": (1024, 64, 64, np.float32, True, False, None),
    "d64-causal-q-and-k-padded": (520, 64, 64, np.float32, True, False,
                                  None),
    "d64-causal-padding-mask": (1024, 64, 64, np.float32, True, True, None),
    "d64-full-2x2-tiles": (1024, 64, 64, np.float32, False, False, None),
    "d64-full-padding-mask": (1024, 64, 64, np.float32, False, True, None),
    "d64-causal-bq256-bk512": (1024, 64, 64, np.float32, True, False, 256),
    "d64-causal-bf16": (1024, 64, 64, jnp.bfloat16, True, False, None),
    # 192 / 128 (and 128 / 128): not a power of two, the f32 multiply.
    "d192-causal-2x2-tiles": (1024, 192, 128, np.float32, True, False,
                              None),
    "d192-full-padding-mask": (600, 192, 128, np.float32, False, True,
                               None),
    "d192-causal-bf16": (1024, 192, 128, jnp.bfloat16, True, False, None),
    "d128-causal-q-and-k-padded": (700, 128, 128, np.float32, True, False,
                                   None),
}


@pytest.mark.parametrize("case", list(_GEOMETRY_CASES))
def test_flash_default_geometry_every_branch(case):
    """Forward and gradients against the dense reference at the tile
    sizes `_blocks` gives: plain tiles split at the diagonal (one
    select), the masked twin (padding mask; padded keys, which also pad
    the queries), non-causal, block_q != block_k, both scale forms,
    f32 and bf16 operands."""
    S, D, Dv, dtype, causal, use_mask, block_q = _GEOMETRY_CASES[case]
    q, k, v = _qkv(B=1, S=S, H=1, D=D, Dv=Dv, dtype=dtype, seed=S + D)
    jmask = None
    if use_mask:
        mask = np.ones((1, S), np.float32)
        mask[0, S - S // 5:] = 0.0
        jmask = jnp.asarray(mask)
    bf16 = dtype == jnp.bfloat16
    got = flash_attention(q, k, v, jmask, causal=causal, block_q=block_q,
                          interpret=True)
    assert got.dtype == q.dtype and got.shape == (1, S, 1, Dv)
    want = dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal=causal, mask=jmask)
    tol = 2e-2 if bf16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)
    _assert_grads_match(q, k, v, jmask, causal, block_q,
                        tol=6e-2 if bf16 else 2e-4)


@pytest.mark.parametrize("S,Dqk,Dv,want", [
    (64, 64, 64, (64, 64)),          # one tile: the block is the sequence
    (130, 64, 64, (130, 130)),
    (511, 64, 64, (511, 511)),
    (512, 64, 64, (512, 512)),
    (513, 64, 64, (512, 512)),       # pads to 1024
    (1024, 64, 64, (512, 512)),
    (2048, 64, 64, (512, 512)),      # was 256 x 512 until PR 31
    (4096, 64, 64, (512, 512)),
    (8192, 128, 128, (512, 512)),
    (4096, 192, 128, (512, 512)),
])
def test_blocks_table(S, Dqk, Dv, want):
    assert fa._blocks(S, Dqk, Dv) == want


@pytest.mark.parametrize("D,folds", [
    (16, True), (64, True), (256, True),
    (32, False), (96, False), (128, False), (192, False),
])
def test_scale_fold_only_where_the_scale_is_a_power_of_two(D, folds,
                                                           monkeypatch):
    """The fold must be exact: taken at 1/4, 1/8, 1/16 and nowhere
    else. Where it is taken, forcing the f32 multiply instead changes
    no bit of the output or of a gradient (bf16 operands: the case a
    second rounding would show in); where it is not, the kernels run
    the f32 form, and a forced fold would have rounded Q and K again."""
    scale = 1.0 / float(np.sqrt(D))
    assert fa._folds_exactly(scale) is folds
    if D not in (64, 192):
        return
    q, k, v = _qkv(B=1, S=256, H=1, D=D, Dv=64, dtype=jnp.bfloat16, seed=D)

    def run():
        f = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=True)
        out, vjp = jax.vjp(f, q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *vjp(out))]

    taken = run()
    monkeypatch.setattr(fa, "_folds_exactly", lambda s: False)
    as_f32_multiply = run()
    monkeypatch.setattr(fa, "_folds_exactly", lambda s: True)
    as_fold = run()
    same = lambda a, b: all(np.array_equal(x, y) for x, y in zip(a, b))
    assert same(taken, as_f32_multiply)
    assert same(taken, as_fold) is folds


# ------------------------------------------- grouped heads and a window

@pytest.fixture
def small_tiles(monkeypatch):
    """16 x 16 tiles, so that a sequence of 80 positions is five tiles
    each way at interpreter cost. The grouped / windowed entries sit
    behind a module-level jit keyed by shapes, not by the tile table:
    drop what it holds before and after."""
    def clear():
        fa._fwd_call_once.clear_cache()
        fa._bwd_call_once.clear_cache()

    clear()
    monkeypatch.setattr(fa, "_blocks", lambda S, Dqk, Dv: (16, 16))
    yield
    clear()


def _windowed_dense(q, k, v, window, mask=None):
    from horovod_tpu.ops.attention import dense_attention as door_dense

    return door_dense(q, k, v, causal=True, window=window, mask=mask)


def _assert_flash_matches_windowed_dense(q, k, v, window, block_q=None,
                                         mask=None, tol=2e-5):
    """Forward and all three gradients, a cotangent on every position."""
    w = jnp.asarray(np.random.RandomState(7).randn(
        *q.shape[:3], v.shape[-1]), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, True, block_q, True, window)

    got, vjp = jax.vjp(flash, q, k, v)
    want, vjp_dense = jax.vjp(
        lambda q, k, v: _windowed_dense(q, k, v, window, mask), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    for name, a, b in zip("qkv", vjp(w), vjp_dense(w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=10 * tol, atol=10 * tol,
                                   err_msg=f"d{name}")


def _grouped_qkv(S, H, H_kv, D=16, B=1, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, S, h, D), jnp.float32)
                 for h in (H, H_kv, H_kv))


@pytest.mark.parametrize("window", [None, 10, 16, 24, 200],
                         ids=["none", "under-a-tile", "a-tile",
                              "a-tile-and-a-half", "over-S"])
@pytest.mark.parametrize("H_kv", [24, 4, 3], ids=["H", "H/6", "H/8"])
def test_grouped_heads_and_window_match_dense(small_tiles, H_kv, window):
    """Five tiles each way: query head h attends key/value head
    h // (H / H_kv), key j is visible iff 0 <= i - j < window; dK and dV
    are the group's sums."""
    q, k, v = _grouped_qkv(80, 24, H_kv)
    _assert_flash_matches_windowed_dense(q, k, v, window)


@pytest.mark.parametrize("case", ["bq-under-bk", "q-and-k-padded",
                                  "padding-mask", "one-key-tile"])
def test_window_on_the_masked_paths(small_tiles, monkeypatch, case):
    """The window where the mask-free body may not run (padded keys, a
    padding mask), at block_q != block_k, and within one key tile."""
    S, block_q, mask = 80, None, None
    if case == "bq-under-bk":
        monkeypatch.setattr(fa, "_blocks", lambda S, Dqk, Dv: (16, 32))
        S = 96
    elif case == "q-and-k-padded":
        S = 75
    elif case == "padding-mask":
        mask = np.ones((1, S), np.float32)
        mask[0, 60:] = 0.0
        mask = jnp.asarray(mask)
    else:
        monkeypatch.setattr(fa, "_blocks", lambda S, Dqk, Dv: (16, 128))
    q, k, v = _grouped_qkv(S, 4, 2, seed=1)
    _assert_flash_matches_windowed_dense(q, k, v, 24, block_q, mask)


def test_window_and_groups_at_the_default_geometry():
    """512 x 512 tiles as `_blocks` gives them, four each way, a window
    of a tile and a half; 6 query heads over one key/value head."""
    q, k, v = _grouped_qkv(2048, 6, 1, seed=2)
    _assert_flash_matches_windowed_dense(q, k, v, 768)


def test_key_tiles_outside_the_window_are_skipped_not_masked(small_tiles):
    """Values poisoned with NaN in every key tile that query tile 3's
    band does not intersect: a kernel that visited those tiles and
    masked their scores would still multiply 0 by NaN. Its rows of the
    output and of dQ stay finite; so do dK and dV of key tile 1 when the
    cotangent is poisoned on every query tile outside its band."""
    S, W, tile = 80, 24, 16
    q, k, v = _grouped_qkv(S, 4, 2, seed=3)
    rows = slice(3 * tile, 4 * tile)          # queries 48-63 see keys 25-63
    poisoned = v.at[:, :tile].set(jnp.nan).at[:, 4 * tile:].set(jnp.nan)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, True, None, True, W)

    out, vjp = jax.vjp(flash, q, k, poisoned)
    cot = jnp.zeros_like(out).at[:, rows].set(1.0)
    dq, _, _ = vjp(cot)
    assert np.isfinite(np.asarray(out[:, rows])).all()
    assert np.isfinite(np.asarray(dq[:, rows])).all()
    assert np.isnan(np.asarray(out[:, :tile])).any()
    # Key tile 1 (keys 16-31) is seen by queries 16-54: tiles 1 to 3.
    out, vjp = jax.vjp(flash, q, k, v)
    cot = jnp.ones_like(out).at[:, :tile].set(jnp.nan).at[
        :, 4 * tile:].set(jnp.nan)
    _, dk, dv = vjp(cot)
    keys = slice(tile, 2 * tile)
    assert np.isfinite(np.asarray(dk[:, keys])).all()
    assert np.isfinite(np.asarray(dv[:, keys])).all()


@pytest.mark.parametrize("S,window,want", [
    (8192, None, (136, 136)),    # 16 x 17 / 2 tiles under the diagonal
    (8192, 512, (31, 136)),      # two a query tile but the first
    (8192, 514, (45, 136)),      # two keys further: a third tile each
    (2048, 512, (7, 10)),
    (300, 64, (1, 1)),
])
def test_key_tiles_counts_the_forward_sweeps(S, window, want):
    assert fa.key_tiles(S, 128, 128, window) == want


# sha256 of `str(jax.make_jaxpr(...))` of one forward and one backward
# call, bf16, causal, no mask, made with the kernels as they stood
# before they took grouped heads and windows (PR 34's tree): a call
# with H_kv == H and no window is still that kernel, to the letter. To
# make them anew after a deliberate change to the kernels:
# `_older_calls_jaxpr_sha(...)` on the tree before the change.
_OLDER_CALLS = {
    (4, 4096, 12, 64, 64):
        "32a3237c524167f810395c4f0de9e08059c4129b06b995d06b991ef11c3afe2c",
    (8, 2048, 12, 64, 64):
        "7d2234b6a0479a5f9fa2b6ab150f7b4ed477ebc2aeae84e2d0728cab51b09310",
    (2, 4096, 32, 192, 128):
        "700bde44d0450fc16b2888f853c1d6b4d0ddea308ede4a4eb159360b4a67346e",
}


def _older_calls_jaxpr_sha(B, S, H, Dqk, Dv):
    import hashlib

    q = jax.ShapeDtypeStruct((B, S, H, Dqk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, S, H, Dv), jnp.bfloat16)

    def both(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, None, True, None, False), q, k, v)
        return out, vjp(out)

    return hashlib.sha256(
        str(jax.make_jaxpr(both)(q, q, v)).encode()).hexdigest()


@pytest.mark.parametrize("shape", list(_OLDER_CALLS),
                         ids=["gpt2-s4096", "gpt2-s2048", "joyai"])
def test_ungrouped_unwindowed_call_is_the_kernel_it_was(shape):
    assert _older_calls_jaxpr_sha(*shape) == _OLDER_CALLS[shape]


def test_transformer_flash_impl_matches_dense():
    """Model-level: attn_impl='flash' produces the same forward as
    attn_impl='dense' (incl. padding mask)."""
    import dataclasses

    from horovod_tpu.models.transformer import (
        BERT_CONFIGS,
        TransformerEncoder,
    )

    base = dataclasses.replace(
        BERT_CONFIGS["bert-tiny"], max_len=64, n_layers=1,
        dtype=jnp.float32, param_dtype=jnp.float32,
        logits_dtype=jnp.float32,
    )
    ids = np.random.RandomState(0).randint(0, 1000, (2, 64), np.int32)
    mask = np.ones((2, 64), np.float32)
    mask[0, 40:] = 0.0

    m_dense = TransformerEncoder(dataclasses.replace(base,
                                                     attn_impl="dense"))
    variables = m_dense.init(jax.random.PRNGKey(0), ids, mask=mask)
    want = m_dense.apply(variables, ids, mask=mask)

    m_flash = TransformerEncoder(dataclasses.replace(base,
                                                     attn_impl="flash"))
    got = m_flash.apply(variables, ids, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_under_gspmd_mesh_is_sharded_and_correct():
    """Under a dp x tp (x idle sp) mesh the dispatch manualizes batch/head axes with
    shard_map (an opaque pallas_call would otherwise force GSPMD to
    replicate); results match the dense path."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import (
        BERT_CONFIGS,
        TransformerEncoder,
    )
    from horovod_tpu.parallel.mesh import create_mesh

    base = dataclasses.replace(
        BERT_CONFIGS["bert-tiny"], max_len=64, n_layers=1,
        dtype=jnp.float32, param_dtype=jnp.float32,
        logits_dtype=jnp.float32,
    )
    ids = np.random.RandomState(0).randint(0, 1000, (4, 64), np.int32)
    mask = np.ones((4, 64), np.float32)
    mask[0, 40:] = 0.0

    m_dense = TransformerEncoder(dataclasses.replace(base,
                                                     attn_impl="dense"))
    variables = m_dense.init(jax.random.PRNGKey(0), ids, mask=mask)
    want = m_dense.apply(variables, ids, mask=mask)

    mesh = create_mesh({"dp": 2, "tp": 2, "sp": 2})
    m_flash = TransformerEncoder(dataclasses.replace(base,
                                                     attn_impl="flash"))
    with _set_mesh(mesh):
        got = jax.jit(lambda v, i, mk: m_flash.apply(v, i, mask=mk))(
            variables, ids, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_with_flash_matches_dense(causal):
    """sp_use_flash: Ulysses' per-head-group attention runs through the
    Pallas kernel inside shard_map and still matches dense."""
    import functools

    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.mesh import create_mesh
    from horovod_tpu.parallel.ulysses import ulysses_attention
    from horovod_tpu.utils.compat import shard_map

    rng = np.random.RandomState(0)
    B, S, H, D = 2, 64, 4, 32
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
               for _ in range(3))
    mask = np.ones((B, S), np.float32)
    mask[0, 40:] = 0.0
    mesh = create_mesh({"dp": 2, "sp": 4})
    want = dense_attention(q, k, v, causal=causal, mask=jnp.asarray(mask))

    fn = shard_map(
        lambda q, k, v, m: ulysses_attention(
            q, k, v, axis_name="sp", causal=causal, mask=m,
            use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "sp"),) * 4,
        out_specs=P(None, "sp"),
    )
    got = jax.jit(fn)(q, k, v, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_model_ulysses_flash_on_dp_sp_mesh():
    """Model-level sp_use_flash on a dp x sp mesh: the dispatch
    manualizes dp alongside sp (the opaque pallas_call would otherwise
    replicate per dp rank) and matches the dense forward."""
    import dataclasses

    from horovod_tpu.models.transformer import (
        BERT_CONFIGS,
        TransformerEncoder,
    )
    from horovod_tpu.parallel.mesh import create_mesh

    base = dataclasses.replace(
        BERT_CONFIGS["bert-tiny"], max_len=64, n_layers=1, n_heads=4,
        dtype=jnp.float32, param_dtype=jnp.float32,
        logits_dtype=jnp.float32,
    )  # 4 heads: Ulysses needs n_heads divisible by sp
    ids = np.random.RandomState(0).randint(0, 1000, (4, 64), np.int32)
    mask = np.ones((4, 64), np.float32)
    mask[0, 40:] = 0.0

    m_dense = TransformerEncoder(dataclasses.replace(base,
                                                     attn_impl="dense"))
    variables = m_dense.init(jax.random.PRNGKey(0), ids, mask=mask)
    want = m_dense.apply(variables, ids, mask=mask)

    mesh = create_mesh({"dp": 2, "sp": 4})
    m_uf = TransformerEncoder(dataclasses.replace(
        base, attn_impl="ulysses", sp_use_flash=True))
    with _set_mesh(mesh):
        got = jax.jit(lambda v, i, mk: m_uf.apply(v, i, mask=mk))(
            variables, ids, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
