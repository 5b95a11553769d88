"""Native C++ core tests: every kernel is verified against its NumPy
fallback (the reference's pattern of validating Adasum against a NumPy
model, test/test_adasum_pytorch.py).

Property-style coverage (docs/native.md):

* reduce/reduce_into/reduce_strided — BITWISE equality vs the ufunc
  fallback over every dtype x op combo at odd/empty/unaligned sizes;
* codec passes (bf16/fp16/int8) — bitwise native-vs-fallback parity on
  adversarial bit patterns (subnormals, ties, inf/NaN payloads) plus
  fp32-tolerance roundtrips;
* error-feedback residual update — bitwise vs np.subtract+nan_to_num;
* graceful decline: non-contiguous / read-only / mismatched inputs
  return False/None so callers run the numpy path;
* HOROVOD_DISABLE_NATIVE honored per call by every wrapper.
"""
import os

import numpy as np
import pytest

import horovod_tpu.cc.native as native
from horovod_tpu.common import compression
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.backend.base import _reduce
from horovod_tpu.ops.adasum import adasum_numpy

try:
    import ml_dtypes

    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - baked into the image
    _BF16 = None

# The numpy mirror of each native op (sequential left fold — the order
# the kernels accumulate in, so float results must match bitwise).
_UFUNC = {"sum": np.add, "min": np.minimum, "max": np.maximum,
          "prod": np.multiply}

ALL_DTYPES = [np.dtype(d) for d in (np.float32, np.float64, np.int32,
                                    np.int64, np.uint8, np.float16)]
if _BF16 is not None:
    ALL_DTYPES.append(_BF16)

ODD_SIZES = [0, 1, 3, 257, 1023]


def _rand(dtype, n, seed):
    rng = np.random.RandomState(seed)
    if np.issubdtype(dtype, np.integer):
        # Small positives: prod stays meaningful, u8 wraps identically
        # in C and numpy (mod-256 both sides).
        return rng.randint(1, 5, n).astype(dtype)
    return (rng.rand(n).astype(np.float32) + 0.5).astype(dtype)


@pytest.fixture(scope="module", autouse=True)
def require_native():
    # These tests compare native against fallback, so they must run the
    # native kernels even when the whole suite is driven under
    # HOROVOD_DISABLE_NATIVE=1 (the ci.sh fallback-parity arm): unset
    # it for this module only.
    saved = os.environ.pop("HOROVOD_DISABLE_NATIVE", None)
    # The adaptive size floor would route tiny arrays to numpy on a
    # single-core box; pin it to 0 so every size exercises the kernels.
    saved_floor = os.environ.get("HOROVOD_NATIVE_REDUCE_MIN_BYTES")
    os.environ["HOROVOD_NATIVE_REDUCE_MIN_BYTES"] = "0"
    try:
        # g++ is part of the baked toolchain; the build must succeed.
        assert native.available(), "native core failed to build"
        yield
    finally:
        if saved is not None:
            os.environ["HOROVOD_DISABLE_NATIVE"] = saved
        if saved_floor is None:
            os.environ.pop("HOROVOD_NATIVE_REDUCE_MIN_BYTES", None)
        else:
            os.environ["HOROVOD_NATIVE_REDUCE_MIN_BYTES"] = saved_floor


# -- k-way reduce -------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                   np.int64])
@pytest.mark.parametrize("op", ["sum", "min", "max", "prod"])
def test_reduce_matches_numpy(op, dtype):
    rng = np.random.RandomState(0)
    if np.issubdtype(dtype, np.integer):
        arrays = [rng.randint(1, 5, 257).astype(dtype) for _ in range(4)]
    else:
        arrays = [rng.rand(257).astype(dtype) + 0.5 for _ in range(4)]
    got = native.reduce_arrays(op, arrays)
    ref = {
        "sum": lambda: np.sum(arrays, axis=0, dtype=dtype),
        "min": lambda: np.minimum.reduce(arrays),
        "max": lambda: np.maximum.reduce(arrays),
        "prod": lambda: np.prod(np.stack(arrays), axis=0, dtype=dtype),
    }[op]()
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert got.dtype == dtype


@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=str)
@pytest.mark.parametrize("op", ["sum", "min", "max", "prod"])
def test_reduce_kway_bitwise_widened_dtypes(op, dtype):
    """The widened table (u8/f16/bf16) reduces bitwise like the
    sequential ufunc fold the numpy fallback runs."""
    arrays = [_rand(dtype, 257, 30 + i) for i in range(4)]
    got = native.reduce_arrays(op, arrays)
    assert got is not None and got.dtype == dtype
    ref = arrays[0].copy()
    for a in arrays[1:]:
        _UFUNC[op](ref, a, out=ref)
    assert got.tobytes() == ref.tobytes()


def test_reduce_large_parallel_path():
    rng = np.random.RandomState(1)
    arrays = [rng.rand(1 << 18).astype(np.float32) for _ in range(3)]
    got = native.reduce_arrays("sum", arrays)
    np.testing.assert_allclose(got, np.sum(arrays, axis=0), rtol=1e-5)


def test_reduce_unsupported_dtype_falls_back():
    # complex64 is genuinely outside the dtype table (u8/f16/bf16 are
    # native now — docs/native.md).
    arrays = [np.ones(4, np.complex64) for _ in range(2)]
    assert native.reduce_arrays("sum", arrays) is None
    # _reduce still works through the NumPy path.
    out = _reduce(ReduceOp.SUM, arrays)
    np.testing.assert_array_equal(out, np.full(4, 2, np.complex64))


# -- in-place segment reduce (the ring's recv+reduce step) --------------
@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=str)
@pytest.mark.parametrize("op", ["sum", "min", "max", "prod"])
@pytest.mark.parametrize("n", ODD_SIZES)
def test_reduce_into_bitwise_vs_ufunc(op, dtype, n):
    tgt = _rand(dtype, n, 10)
    src = _rand(dtype, n, 11)
    ref = tgt.copy()
    if n:
        _UFUNC[op](ref, src, out=ref)
    assert native.reduce_into(op, tgt, src)
    assert tgt.tobytes() == ref.tobytes()


def test_reduce_into_unaligned_buffers():
    """Byte-offset views (arena slices land anywhere): still bitwise."""
    n = 257
    raw_t, raw_s = bytearray(4 * n + 1), bytearray(4 * n + 3)
    tgt = np.frombuffer(raw_t, np.float32, n, offset=1)
    src = np.frombuffer(raw_s, np.float32, n, offset=3)
    tgt[:] = _rand(np.float32, n, 40)
    src[:] = _rand(np.float32, n, 41)
    ref = tgt.copy()
    np.add(ref, src, out=ref)
    assert native.reduce_into("sum", tgt, src)
    assert tgt.tobytes() == ref.tobytes()


def test_reduce_into_declines_bad_inputs():
    good = np.ones(10, np.float32)
    # Non-contiguous src / tgt.
    assert not native.reduce_into("sum", good.copy(),
                                  np.arange(20, dtype=np.float32)[::2])
    assert not native.reduce_into("sum",
                                  np.ones(20, np.float32)[::2], good)
    # Read-only target.
    ro = np.ones(10, np.float32)
    ro.setflags(write=False)
    assert not native.reduce_into("sum", ro, good)
    # dtype / size mismatches.
    assert not native.reduce_into("sum", good.copy(),
                                  np.ones(10, np.float64))
    assert not native.reduce_into("sum", good.copy(),
                                  np.ones(11, np.float32))
    assert not native.reduce_into("sum", np.ones(4, np.complex64),
                                  np.ones(4, np.complex64))


def test_reduce_into_size_floor(monkeypatch):
    """HOROVOD_NATIVE_REDUCE_MIN_BYTES routes small arrays back to
    numpy (the ctypes round-trip loses to in-cache ufuncs); the env
    var is read per call so tests and operators can flip it live."""
    tgt = np.ones(256, np.float32)
    src = np.ones(256, np.float32)
    monkeypatch.setenv("HOROVOD_NATIVE_REDUCE_MIN_BYTES", str(1 << 20))
    assert not native.reduce_into("sum", tgt, src)
    monkeypatch.setenv("HOROVOD_NATIVE_REDUCE_MIN_BYTES", "0")
    assert native.reduce_into("sum", tgt, src)
    np.testing.assert_array_equal(tgt, np.full(256, 2, np.float32))


# -- fused arena gather-reduce ------------------------------------------
def _strided_case(nsrc, n, dtype, seed):
    """Arena-shaped byte buffer: nsrc peer slices at offset + r*stride,
    deliberately odd offset/stride, surrounded by random junk the
    kernel must not read or write."""
    rng = np.random.RandomState(seed)
    itemsize = np.dtype(dtype).itemsize
    off0, stride = 24 + itemsize, n * itemsize + 40
    nbytes = off0 + max(nsrc - 1, 0) * stride + n * itemsize + 8
    buf = np.frombuffer(bytearray(rng.bytes(nbytes)), np.uint8).copy()
    srcs = []
    for r in range(nsrc):
        a = _rand(dtype, n, seed + 1 + r)
        start = off0 + r * stride
        buf[start:start + n * itemsize] = a.view(np.uint8)
        srcs.append(a)
    return buf, off0, stride, srcs


@pytest.mark.parametrize("dtype",
                         [np.dtype(np.float32), np.dtype(np.float16)]
                         + ([_BF16] if _BF16 is not None else []),
                         ids=str)
@pytest.mark.parametrize("op", ["sum", "min", "max", "prod"])
def test_reduce_strided_init_bitwise(op, dtype):
    n = 257
    buf, off, stride, srcs = _strided_case(5, n, dtype, 20)
    out = np.empty(n, dtype)
    assert native.reduce_strided(op, buf, off, stride, 5, -1, out,
                                 init=True)
    ref = srcs[0].copy()
    for s in srcs[1:]:
        _UFUNC[op](ref, s, out=ref)
    assert out.tobytes() == ref.tobytes()


@pytest.mark.parametrize("skip", [0, 2, 3])
def test_reduce_strided_accumulate_with_skip(skip):
    """init=False accumulates into the existing out, skipping the root
    slot — the hierarchical reduce_to_member shape."""
    n = 129
    buf, off, stride, srcs = _strided_case(4, n, np.float32, 21)
    out = _rand(np.float32, n, 99)
    ref = out.copy()
    assert native.reduce_strided("sum", buf, off, stride, 4, skip, out,
                                 init=False)
    for r, s in enumerate(srcs):
        if r != skip:
            np.add(ref, s, out=ref)
    assert out.tobytes() == ref.tobytes()


def test_reduce_strided_single_source_is_copy():
    n = 63
    buf, off, stride, srcs = _strided_case(1, n, np.float32, 22)
    out = np.empty(n, np.float32)
    assert native.reduce_strided("sum", buf, off, stride, 1, -1, out,
                                 init=True)
    assert out.tobytes() == srcs[0].tobytes()


def test_reduce_strided_declines_out_of_bounds():
    buf = np.zeros(100, np.uint8)
    out = np.empty(30, np.float32)
    # offset + (nsrc-1)*stride + nbytes = 0 + 100 + 120 > 100.
    assert not native.reduce_strided("sum", buf, 0, 50, 3, -1, out,
                                     init=True)
    # init=True with every source skipped has no seed.
    buf2, off, stride, _ = _strided_case(1, 8, np.float32, 23)
    out2 = np.empty(8, np.float32)
    assert not native.reduce_strided("sum", buf2, off, stride, 1, 0,
                                     out2, init=True)


# -- fusion pack/unpack -------------------------------------------------
def test_pack_unpack_roundtrip_mixed_shapes():
    rng = np.random.RandomState(2)
    arrays = [rng.rand(*s).astype(np.float32)
              for s in [(3, 4), (7,), (2, 2, 2), (1,)]]
    packed = native.pack(arrays)
    assert packed.nbytes == sum(a.nbytes for a in arrays)
    outs = native.unpack(packed, [a.shape for a in arrays], np.float32)
    for a, b in zip(arrays, outs):
        np.testing.assert_array_equal(a, b)


def test_pack_with_empty_segment():
    arrays = [np.arange(3, dtype=np.float32), np.empty(0, np.float32),
              np.ones(2, np.float32)]
    packed = native.pack(arrays)
    assert packed is not None
    assert packed.view(np.float32).tolist() == [0.0, 1.0, 2.0, 1.0, 1.0]


def test_pack_large_parallel_path():
    rng = np.random.RandomState(3)
    arrays = [rng.rand(1 << 17).astype(np.float32) for _ in range(8)]
    packed = native.pack(arrays).view(np.float32)
    np.testing.assert_array_equal(
        packed, np.concatenate([a.ravel() for a in arrays])
    )


# -- wire codec passes --------------------------------------------------
def _adversarial_f32():
    """fp32 arrays hitting every rounding edge: signed zeros, inf, NaN
    payloads, fp16 overflow boundary (65504/65520), fp16 subnormal
    boundary (2^-24/2^-25), fp32 subnormals, RNE ties, plus a dense
    sweep of raw random bit patterns."""
    rng = np.random.RandomState(7)
    specials = np.array(
        [0.0, -0.0, np.inf, -np.inf, np.nan, 65504.0, 65519.0, 65520.0,
         2.0 ** -24, 2.0 ** -25, -(2.0 ** -24), 1e-40, -1e-40, 1.0,
         -1.0, 3.14159, 1e38, -1e38], np.float32)
    bits = rng.randint(0, 2 ** 32, 4096,
                       dtype=np.uint32).view(np.float32)
    return [specials, bits, np.concatenate([specials, bits]),
            np.zeros(0, np.float32),
            np.full(33, np.nan, np.float32),
            np.full(5, np.inf, np.float32)]


@pytest.mark.parametrize("codec_name", ["bf16", "fp16", "int8"])
def test_codec_native_vs_fallback_bitwise(codec_name, monkeypatch):
    """The native encode/decode must emit the exact bytes the numpy
    fallback emits — ranks mixing native and fallback builds would
    otherwise disagree on the wire."""
    codec = compression.codec_by_name(codec_name)
    for i, a in enumerate(_adversarial_f32()):
        monkeypatch.delenv("HOROVOD_DISABLE_NATIVE", raising=False)
        enc_nat = codec.encode(a)
        monkeypatch.setenv("HOROVOD_DISABLE_NATIVE", "1")
        enc_fb = codec.encode(a)
        assert enc_nat.tobytes() == enc_fb.tobytes(), (codec_name, i)
        dec_fb = codec.decode(enc_fb, a.size)
        monkeypatch.delenv("HOROVOD_DISABLE_NATIVE")
        dec_nat = codec.decode(enc_nat, a.size)
        assert dec_nat.tobytes() == dec_fb.tobytes(), (codec_name, i)


def test_fp16_decode_exhaustive_bitwise():
    """All 65536 half patterns — subnormals, NaN payloads, the lot."""
    bits = np.arange(65536, dtype=np.uint16)
    got = native.fp16_decode(bits.tobytes(), bits.size)
    ref = bits.view(np.float16).astype(np.float32)
    assert got.tobytes() == ref.tobytes()


def test_bf16_decode_exhaustive_bitwise():
    if _BF16 is None:
        pytest.skip("ml_dtypes not available")
    bits = np.arange(65536, dtype=np.uint16)
    got = native.bf16_decode(bits.tobytes(), bits.size)
    ref = np.frombuffer(bits.tobytes(), dtype=_BF16).astype(np.float32)
    assert got.tobytes() == ref.tobytes()


@pytest.mark.parametrize("codec_name,rtol", [("bf16", 1.0 / 128),
                                             ("fp16", 1e-3),
                                             ("int8", None)])
def test_codec_roundtrip_tolerance(codec_name, rtol):
    rng = np.random.RandomState(8)
    a = (rng.randn(1001) * 10).astype(np.float32)
    codec = compression.codec_by_name(codec_name)
    out = codec.decode(codec.encode(a), a.size)
    if rtol is None:  # int8: absolute error bounded by scale/2
        scale = float(np.max(np.abs(a))) / 127.0
        assert float(np.max(np.abs(out - a))) <= scale * 0.5 + 1e-7
    else:
        np.testing.assert_allclose(out, a, rtol=rtol, atol=1e-6)


def test_codec_wrappers_decline_bad_inputs():
    noncontig = np.ones(20, np.float32)[::2]
    assert native.bf16_encode(noncontig) is None
    assert native.fp16_encode(noncontig) is None
    assert native.int8_encode(noncontig) is None
    wrong_dtype = np.ones(4, np.float64)
    assert native.bf16_encode(wrong_dtype) is None


# -- error-feedback residual update -------------------------------------
def test_ef_update_bitwise_vs_numpy():
    rng = np.random.RandomState(9)
    pre = rng.randn(513).astype(np.float32)
    wire = (pre + rng.randn(513).astype(np.float32) * 0.01).astype(
        np.float32)
    pre[3], wire[7] = np.inf, np.nan
    pre[11], wire[11] = -np.inf, np.inf
    res = np.empty_like(pre)
    assert native.ef_update(res, pre, wire)
    ref = np.subtract(pre, wire)
    np.nan_to_num(ref, copy=False, nan=0.0, posinf=0.0, neginf=0.0)
    assert res.tobytes() == ref.tobytes()


def test_ef_update_declines_bad_inputs():
    f32 = np.ones(4, np.float32)
    assert not native.ef_update(np.ones(4, np.float64), f32, f32)
    assert not native.ef_update(f32.copy(), f32, np.ones(5, np.float32))
    ro = np.ones(4, np.float32)
    ro.setflags(write=False)
    assert not native.ef_update(ro, f32, f32)


def test_error_feedback_store_matches_fallback(monkeypatch):
    """ErrorFeedback.update lands the same residual either way."""
    rng = np.random.RandomState(12)
    pre = rng.randn(257).astype(np.float32)
    wire = (pre * 0.5).astype(np.float32)
    pre[5] = np.inf

    def run():
        ef = compression.ErrorFeedback()
        ef.put("k", np.zeros(257, np.float32))
        ef.update("k", pre.copy(), wire.copy())
        return ef.get("k", 257).copy()

    got_native = run()
    monkeypatch.setenv("HOROVOD_DISABLE_NATIVE", "1")
    got_fb = run()
    assert got_native.tobytes() == got_fb.tobytes()


# -- adasum -------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 4, 8])
def test_adasum_matches_numpy_oracle(n):
    rng = np.random.RandomState(4)
    arrays = [rng.randn(33).astype(np.float32) for _ in range(n)]
    got = native.adasum(arrays)
    ref = adasum_numpy(arrays)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)
        assert g.dtype == np.float32


def test_adasum_identical_vectors_identity():
    """n identical vectors adasum-combine to the same vector."""
    v = np.linspace(-1, 1, 17).astype(np.float64)
    got = native.adasum([v.copy() for _ in range(4)])
    for g in got:
        np.testing.assert_allclose(g, v, rtol=1e-12)


def test_adasum_rejects_non_power_of_two():
    assert native.adasum([np.ones(4) for _ in range(3)]) is None


# -- dispatch, status, disable ------------------------------------------
def test_reduce_through_backend_dispatch():
    """_reduce uses the native path for f32 and agrees with NumPy."""
    rng = np.random.RandomState(5)
    arrays = [rng.rand(100).astype(np.float32) for _ in range(3)]
    out = _reduce(ReduceOp.AVERAGE, arrays)
    np.testing.assert_allclose(out, np.mean(arrays, axis=0), rtol=1e-6)


def test_status_and_inventory_shape():
    st = native.status()
    assert {"built", "loaded", "disabled", "abi", "threads",
            "kernels"} <= set(st)
    assert st["built"] and st["loaded"] and not st["disabled"]
    assert st["abi"] == native.ABI_VERSION
    inv = native.kernel_inventory()
    assert set(inv) == set(native._KERNELS)
    assert all(inv.values())
    assert native.threads() >= 1


def test_disable_native_env_all_wrappers(monkeypatch):
    """HOROVOD_DISABLE_NATIVE is honored per call: every wrapper
    reports unavailable while set, no reload dance needed."""
    monkeypatch.setenv("HOROVOD_DISABLE_NATIVE", "1")
    assert native.load() is None
    assert native.reduce_arrays("sum",
                                [np.ones(3, np.float32)] * 2) is None
    tgt = np.ones(3, np.float32)
    assert not native.reduce_into("sum", tgt, tgt.copy())
    out = np.empty(3, np.float32)
    assert not native.reduce_strided("sum", np.zeros(64, np.uint8), 0,
                                     16, 2, -1, out, init=True)
    assert native.bf16_encode(np.ones(3, np.float32)) is None
    assert native.fp16_decode(b"\x00" * 6, 3) is None
    assert native.int8_encode(np.ones(3, np.float32)) is None
    assert not native.ef_update(out, tgt, tgt)
    st = native.status()
    assert st["disabled"] and not st["loaded"]
    monkeypatch.delenv("HOROVOD_DISABLE_NATIVE")
    assert native.available()


def test_first_calls_from_many_threads_do_not_abort():
    """The worker pool is created lazily by whichever thread calls in
    first; threads that lose that race must discard their pool without
    destroying running std::threads (std::terminate → SIGABRT, seen as
    soon as two engines started at once on a many-core host). The race
    exists only before a process's pool does, so every round is a
    forked child (the pool is keyed by pid) of one fresh, still
    single-threaded interpreter."""
    import subprocess
    import sys

    code = (
        "import os, sys, threading\n"
        "import horovod_tpu.cc.native as native\n"
        "assert native.load() is not None\n"
        "def race():\n"
        "    sys.setswitchinterval(1e-6)\n"
        "    gate = threading.Barrier(16)\n"
        "    def first_call():\n"
        "        gate.wait(timeout=30)\n"
        "        assert native.threads() == 16\n"
        "    ts = [threading.Thread(target=first_call) for _ in range(16)]\n"
        "    [t.start() for t in ts]\n"
        "    [t.join(timeout=60) for t in ts]\n"
        "    return any(t.is_alive() for t in ts)\n"
        "for _ in range(20):\n"
        "    pid = os.fork()\n"
        "    if pid == 0:\n"
        "        os._exit(1 if race() else 0)\n"
        "    status = os.waitpid(pid, 0)[1]\n"
        "    assert status == 0, f'child ended with wait status {status}'\n"
    )
    env = {**os.environ, "HOROVOD_NATIVE_THREADS": "16"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1500:]
