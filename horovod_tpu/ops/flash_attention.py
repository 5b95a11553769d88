"""Fused attention as a Pallas TPU kernel.

The hot op of every transformer in the zoo (GPT-2/BERT/ViT,
models/transformer.py) is attention; XLA materializes the (S, S) score
matrix in HBM for the dense path. This kernel streams k-blocks through
a running-softmax accumulator entirely in VMEM: scores never touch HBM,
both matmuls ride the MXU in the input dtype (bf16 fast path, f32
accumulate), and causal q-blocks skip every k-block above the diagonal
(ref: the CUDA fused-scale kernel is the reference's only hand-written
device code, horovod/common/ops/cuda/cuda_kernels.cu:25-77 — the
equivalent TPU move per SURVEY.md §2.7 is Pallas for ops XLA fusion
can't cover).

Measured on one TPU v5e chip (H=8, D=64, bf16, causal): forward 2.5x
the XLA dense path at S=4096; forward+backward 2.3x at S=4096 and ~20x
at S=8192 (where dense spills its (S, S) scores to HBM). Enable per
model with TransformerConfig(attn_impl="flash").

Semantics match parallel/ring.py's dense_attention exactly, including
the padding-mask convention (1 = attend, 0 = pad; fully-masked rows
yield zeros). The backward pass is blockwise Pallas too (Dao et al.
structure): the forward saves only the output and the per-row
logsumexp, and ONE fused kernel (`_dqkv_kernel`, r5) recomputes each
probability tile exactly once while producing dQ, dK, and dV in a
single k-block sweep (dQ rides a persistent VMEM scratch) — so
neither direction ever materializes (S, S) scores in HBM, causal
block-skipping applies in both, and the backward does 5 tile matmuls
instead of the classic two-pass structure's 7. Tiles that cannot be
touched by masking (below-diagonal, no padding) take a stripped
VPU-light body — see `_prep`'s `plain`.

Gradients therefore differentiate the same math; forward numerics agree
with the reference to bf16/f32 tolerance (asserted in
tests/test_flash_attention.py, incl. interpret mode on CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK_Q = 128
NEG_INF = -1e30

def _prec(dtype):
    """Explicit contract precision for in-kernel dots: bf16 (and other
    sub-f32) inputs must use the native MXU path — a global
    jax_default_matmul_precision=float32 would otherwise inject an
    fp32-precision bf16 matmul that Mosaic rejects ("Bad lhs type").
    f32 inputs keep None so the global config still applies to them."""
    return None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_platform import call_by_platform


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, scale: float,
            causal: bool, block_q: int, block_k: int, plain: bool):
    """One (batch*head, q-block) grid step, streaming k-blocks.

    q_ref: (1, block_q, Dqk); k_ref: (1, S_pad, Dqk) and v_ref:
    (1, S_pad, Dv), VMEM-resident; mask_ref: (1, 1, S_pad);
    o_ref: (1, block_q, Dv); lse_ref: (1, 1, block_q) per-row logsumexp
    residual. Dqk and Dv are one number in GPT-2 / BERT / ViT; a latent
    attention's query/key heads are wider than its value heads.

    Flash-style: a fori_loop folds (block_q, block_k) score tiles into a
    running (max, normalizer, accumulator) state, so peak VMEM for
    scores is O(block_q*block_k) regardless of S, and causal q-blocks
    skip every k-block entirely above the diagonal — the canonical
    ~2x FLOP saving for causal attention.

    `plain=True` (no padding mask, keys unpadded): tiles fully below the
    diagonal take a mask-free body — no position iotas, compares, or
    where-selects. At D=64 the per-score softmax VPU work, not the MXU,
    bounds this kernel (docs/benchmarks.md), so stripping the masking
    VPU ops from the ~60% of tiles that never needed them is a direct
    win; only the tiles straddling the diagonal run the masked body.
    """
    qi = pl.program_id(1)

    # Native-dtype matmuls with f32 accumulation: bf16 inputs hit the
    # MXU's fast path; only the accumulator/softmax run in f32.
    q = q_ref[0]                               # (block_q, Dqk)
    D = v_ref.shape[-1]                        # the accumulator's: Dv
    s_pad = k_ref.shape[1]

    def tile(kb, carry, masked):
        acc, m, l = carry
        # Ref-level dynamic slices (Mosaic lowers pl.ds on refs; value-
        # level lax.dynamic_slice is not supported in-kernel).
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q.dtype),
        ) * scale                               # (block_q, block_k) f32
        if masked:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if plain:
                valid = kpos <= qpos
            else:
                m_blk = mask_ref[0, 0, pl.ds(kb * block_k, block_k)]
                valid = m_blk[None, :] > 0      # padded keys masked here
                if causal:
                    valid = jnp.logical_and(valid, kpos <= qpos)
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if masked:
            # Explicit zeroing: an all-masked tile would otherwise turn
            # the NEG_INF plateau into exp(0)=1 rows (same convention as
            # parallel/ring.py _flash_block_update).
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(v_blk.dtype),
        )
        acc = acc * corr[:, None] + pv
        return acc, m_new, l

    num_kb = s_pad // block_k
    if causal:
        # k-blocks whose first key position exceeds this q-block's last
        # query position are entirely masked: skip them.
        last_q = (qi + 1) * block_q - 1
        num_kb = jnp.minimum(num_kb, last_q // block_k + 1)

    carry = (
        jnp.zeros((block_q, D), jnp.float32),
        jnp.full((block_q,), NEG_INF, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
    )
    if plain and causal:
        # Tiles whose last key row sits at/below this q-block's first
        # query row need no causal masking at all.
        n_full = (qi * block_q) // block_k
        carry = jax.lax.fori_loop(
            0, n_full, lambda kb, c: tile(kb, c, masked=False), carry)
        carry = jax.lax.fori_loop(
            n_full, num_kb, lambda kb, c: tile(kb, c, masked=True), carry)
    elif plain:
        carry = jax.lax.fori_loop(
            0, num_kb, lambda kb, c: tile(kb, c, masked=False), carry)
    else:
        carry = jax.lax.fori_loop(
            0, num_kb, lambda kb, c: tile(kb, c, masked=True), carry)
    acc, m, l = carry

    l_safe = jnp.maximum(l, 1e-30)
    o = acc / l_safe[:, None]
    o_ref[0] = o.astype(o_ref.dtype)
    # Per-row logsumexp, the only residual the backward needs beyond the
    # inputs (Dao et al. flash backward): p = exp(s - L) is already
    # normalized.
    lse_ref[0, 0] = m + jnp.log(l_safe)


DEFAULT_BLOCK_K = 512
# Mosaic gives a kernel 16 MiB of VMEM unless told otherwise. The
# backward keeps Q, dO, dQ and an f32 dQ accumulator of the whole
# sequence resident; at heads wider than one 128-lane tile (192 fills
# two) and S = 4096 that is 17.3 MiB. A v5e core has 128 MiB.
WIDE_HEAD_VMEM_BYTES = 64 * 2 ** 20


def _compiler_params(*head_dims: int) -> dict:
    """`pallas_call` keywords: nothing for heads within one lane tile
    (the call, and its lowered text, are then what they always were),
    a raised VMEM limit for wider ones."""
    if max(head_dims) <= 128:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=WIDE_HEAD_VMEM_BYTES)}


def _prep(q, k, v, mask, block_q: int):
    """Shared layout/padding for forward and backward: (B,S,H,D) ->
    (B*H,S,D), each tensor at its own D, with queries padded to a block_q multiple (garbage rows
    sliced off after) and keys/values/mask padded to a block_k multiple
    (padded keys carry mask 0, so they never contribute). Both passes
    MUST use identical block/pad arithmetic for the saved lse residual
    to line up with the backward's blocks.

    Also returns `plain`: True when no padding mask exists and keys
    needed no block padding — the kernels then take the mask-free fast
    path on below-diagonal tiles (the key-validity mask is the only
    thing key padding relies on, so it must force the masked path)."""
    B, S, H, _ = q.shape
    if block_q is None:
        # Measured on v5e (B4 H12 D64, full GPT-2 train step, r5,
        # mask-free fast path + fused single-sweep backward): at
        # S=2048, 256 wins (77.0 ms vs 81.4 at 512 and 96.6 at 128);
        # at S=4096, 512 stays ~25% ahead of 256 (coarser causal
        # skipping amortizes, VMEM pressure per q-block matters less).
        # Below 2048 the finer grid's causal skipping pays: 128. (384
        # and 1024 lose everywhere — Mosaic tiling/VMEM pressure.)
        if S < 2048:
            block_q = DEFAULT_BLOCK_Q
        elif S == 2048:
            block_q = 256
        else:
            block_q = 512
    bq = min(block_q, S)
    bk = min(DEFAULT_BLOCK_K, S)
    pad_q = (-S) % bq
    pad_k = (-S) % bk

    # (B, S, H, D) -> (B*H, S, D): attention is independent per (b, h).
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])

    qb, kb_arr, vb = to_bh(q), to_bh(k), to_bh(v)
    if pad_q:
        qb = jnp.pad(qb, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kb_arr = jnp.pad(kb_arr, ((0, 0), (0, pad_k), (0, 0)))
        vb = jnp.pad(vb, ((0, 0), (0, pad_k), (0, 0)))

    # (B, 1, Sk): the singleton sublane dim satisfies Mosaic's tiling
    # rule for the (1, 1, Sk) block (last two dims must divide (8, 128)
    # or equal the array dims).
    if mask is None:
        mask2 = jnp.ones((B, 1, S), jnp.float32)
    else:
        mask2 = mask.astype(jnp.float32).reshape(B, 1, S)
    if pad_k:
        mask2 = jnp.pad(mask2, ((0, 0), (0, 0), (0, pad_k)))
    plain = mask is None and pad_k == 0
    return (qb, kb_arr, vb, mask2, to_bh, bq, bk, S + pad_q, S + pad_k,
            plain)


def _flash_fwd(q, k, v, mask, causal: bool, block_q: int,
               interpret: Optional[bool]
               ) -> "tuple[jax.Array, jax.Array]":
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))
    qb, kb_arr, vb, mask2, _, bq, bk, Sq, Sk, plain = _prep(q, k, v,
                                                            mask, block_q)
    grid = (B * H, Sq // bq)

    def call(interp: bool):
        return pl.pallas_call(
            functools.partial(_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, plain=plain),
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
                jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
            ],
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
                pl.BlockSpec((1, Sk, Dv), lambda bh, qi: (bh, 0, 0)),
                # mask indexed by batch = bh // H (static H via closure).
                pl.BlockSpec((1, 1, Sk),
                             lambda bh, qi, H=H: (bh // H, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, Dv), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, 1, bq), lambda bh, qi: (bh, 0, qi)),
            ],
            interpret=interp,
            name="flash_attention_fwd",
            **_compiler_params(D, Dv),
        )

    out, lse = call_by_platform(call, qb, kb_arr, vb, mask2,
                                interpret=interpret)

    out = out[:, :S]
    # Slice lse to the real rows too, so the backward's re-pad is the
    # single true padding (padded-row lse is kernel garbage here).
    return out.reshape(B, H, S, Dv).transpose(0, 2, 1, 3), lse[:, :, :S]


def _dqkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, dq_acc, *, scale: float,
                 causal: bool, block_q: int, block_k: int, plain: bool):
    """FUSED backward: grid (B*H, k-block), ki innermost. One sweep
    computes dK/dV for this k-block AND accumulates every q-block's dQ
    contribution into a persistent f32 VMEM scratch (written out once,
    on the last k-block) — so each probability tile is recomputed ONCE
    per backward instead of once per pass, and the dO@V^T `dp` matmul
    is shared between dQ and dK instead of being issued twice (5 tile
    matmuls vs the two-pass structure's 7, and half the exp/VPU work).
    Measured on the GPT-2 seq-2048 v5e step this is the difference
    between ~0.49 and >=0.50 MFU (docs/benchmarks.md).

    The scratch depends on TPU grid semantics: grid steps run
    sequentially with the last dim innermost, so dq_acc persists across
    the ki sweep of one (b, h) program and is re-zeroed at ki=0.
    Padded q rows carry lse=+inf, killing their p rows — which is what
    keeps the `plain` fast path valid under q padding."""
    ki = pl.program_id(1)
    k = k_ref[0]                                 # (bk, Dqk)
    v = v_ref[0]                                 # (bk, Dv)
    D, Dv = k.shape[-1], v.shape[-1]
    sq_pad = q_ref.shape[1]
    num_kb = pl.num_programs(1)

    @pl.when(ki == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(qi, carry, masked):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :]
        L = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q_blk.dtype),
        ) * scale                                # (bq, bk)
        p = jnp.exp(s - L[:, None])
        if masked:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if plain:
                valid = kpos <= qpos
            else:
                m_blk = mask_ref[0, 0]           # (bk,)
                valid = m_blk[None, :] > 0
                if causal:
                    valid = jnp.logical_and(valid, kpos <= qpos)
            p = jnp.where(valid, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(do_blk.dtype),
        )                                        # (bk, Dv)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(v.dtype),
        )                                        # (bq, bk)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q_blk.dtype),
        )                                        # (bk, D)
        dq_acc[pl.ds(qi * block_q, block_q), :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(k.dtype),
        )                                        # (bq, D)
        return dk, dv

    num_qb = sq_pad // block_q
    start_qb = 0
    if causal:
        start_qb = (ki * block_k) // block_q
    carry = (jnp.zeros((block_k, D), jnp.float32),
             jnp.zeros((block_k, Dv), jnp.float32))
    if plain and causal:
        diag_end = jnp.minimum(
            ((ki + 1) * block_k + block_q - 1) // block_q, num_qb)
        carry = jax.lax.fori_loop(
            start_qb, diag_end, lambda qi, c: tile(qi, c, masked=True),
            carry)
        carry = jax.lax.fori_loop(
            diag_end, num_qb, lambda qi, c: tile(qi, c, masked=False),
            carry)
    elif plain:
        carry = jax.lax.fori_loop(
            0, num_qb, lambda qi, c: tile(qi, c, masked=False), carry)
    else:
        carry = jax.lax.fori_loop(
            start_qb, num_qb, lambda qi, c: tile(qi, c, masked=True),
            carry)
    dk, dv = carry
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == num_kb - 1)
    def _flush_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, mask, out, lse, g, causal: bool, block_q: int,
               interpret: Optional[bool]):
    """Blockwise backward: same VMEM-bounded structure as the forward —
    the (S, S) score matrix is never materialized in HBM."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))
    qb, kb_arr, vb, mask2, to_bh, bq, bk, Sq, Sk, plain = _prep(
        q, k, v, mask, block_q)
    pad_q = Sq - S
    dob, ob = to_bh(g), to_bh(out)
    if pad_q:
        zq = ((0, 0), (0, pad_q), (0, 0))
        dob, ob = jnp.pad(dob, zq), jnp.pad(ob, zq)
        # Padded q rows: lse=+big makes p = exp(s - lse) vanish, so they
        # contribute nothing to dK/dV (their own dq rows are sliced off).
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)),
                      constant_values=1e30)

    # delta = rowsum(dO * O) (tiny elementwise; jnp outside the kernel).
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, Sq)

    # Q, K, dQ, dK at Dqk; V, dO, dV at Dv.
    full_q = pl.BlockSpec((1, Sq, D), lambda bh, ki: (bh, 0, 0))
    full_do = pl.BlockSpec((1, Sq, Dv), lambda bh, ki: (bh, 0, 0))
    row_q = pl.BlockSpec((1, 1, Sq), lambda bh, ki: (bh, 0, 0))
    blk_k = pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0))
    blk_v = pl.BlockSpec((1, bk, Dv), lambda bh, ki: (bh, ki, 0))

    def call(interp: bool):
        return pl.pallas_call(
            functools.partial(_dqkv_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, plain=plain),
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, Sk, Dv), v.dtype),
            ],
            grid=(B * H, Sk // bk),
            in_specs=[
                full_q,
                blk_k, blk_v,
                pl.BlockSpec((1, 1, bk),
                             lambda bh, ki, H=H: (bh // H, 0, ki)),
                full_do, row_q, row_q,
            ],
            out_specs=[
                full_q,   # dq: one block per (b, h), flushed on last ki
                blk_k, blk_v,
            ],
            scratch_shapes=[pltpu.VMEM((Sq, D), jnp.float32)],
            interpret=interp,
            name="flash_attention_bwd",
            **_compiler_params(D, Dv),
        )

    dq, dk, dv = call_by_platform(call, qb, kb_arr, vb, mask2, dob, lse,
                                  delta, interpret=interpret)

    def from_bh(x, S_):
        return x[:, :S_].reshape(B, H, S_, x.shape[-1]).transpose(0, 2, 1, 3)

    return from_bh(dq, S), from_bh(dk, S), from_bh(dv, S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, mask=None, causal: bool = True,
                    block_q: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention. q/k: (B, S, H, Dqk), v: (B, S, H, Dv); mask:
    optional (B, S) key validity (1 = attend). Returns (B, S, H, Dv) in
    q.dtype; scores are scaled by 1/sqrt(Dqk). Dqk == Dv in GPT-2 /
    BERT / ViT; a latent attention has 192 beside 128.

    `block_q=None` auto-selects by sequence length (128 below S=2048,
    256 at 2048, 512 beyond — measured full-train-step crossover on
    v5e, r5); both vjp passes resolve it identically in `_prep`.
    `interpret=None` follows ops/pallas_platform.py: the interpreter
    where the call is lowered for the CPU (the tests, the virtual
    8-device mesh), the compiled kernel on anything else."""
    out, _ = _flash_fwd(q, k, v, mask, causal, block_q, interpret)
    return out


def _fwd(q, k, v, mask, causal, block_q, interpret):
    out, lse = _flash_fwd(q, k, v, mask, causal, block_q, interpret)
    return out, (q, k, v, mask, out, lse)


def _bwd(causal, block_q, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    dq, dk, dv = _flash_bwd(q, k, v, mask, out, lse, g, causal, block_q,
                            interpret)
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)
