"""Wire-level gradient compression (ISSUE 12): codec roundtrip error
bounds and edge cases, error-feedback convergence on a quadratic,
coordinator codec-assignment policy, engine integration (negotiated
codec + cache replay + residual lifecycle), compressed ring/star/arena
data planes with cross-rank bitwise agreement, codec-mismatch desync
attribution over real TCP, and env-knob parsing per house convention.
"""
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from horovod_tpu.backend.base import (
    channel_scope,
    current_wire_codec,
    wire_codec_scope,
)
from horovod_tpu.common import compression as C
from horovod_tpu.common import telemetry
from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    TransportError,
)
from horovod_tpu.common.message import (
    Response,
    ResponseType,
)
from horovod_tpu.common.types import DataType, ReduceOp


BF16 = C.codec_by_name("bf16")
FP16 = C.codec_by_name("fp16")
INT8 = C.codec_by_name("int8")


# ---------------------------------------------------------------------------
# codec roundtrip properties

@pytest.mark.parametrize("codec,rel_bound", [(BF16, 2 ** -8),
                                             (FP16, 2 ** -10)])
def test_fixed_width_roundtrip_error_bound(codec, rel_bound):
    rng = np.random.default_rng(7)
    for scale in (1e-3, 1.0, 1e4):
        # magnitudes bounded away from 0 so the bound tests the
        # MANTISSA error, not fp16's subnormal flush near zero
        x = (rng.uniform(0.5, 2.0, 4096)
             * rng.choice([-1.0, 1.0], 4096) * scale).astype(np.float32)
        enc = codec.encode(x)
        assert enc.dtype == np.uint8
        assert enc.nbytes == codec.wire_bytes(x.size) == 2 * x.size
        y = codec.decode(enc, x.size)
        assert y.dtype == np.float32
        rel = np.max(np.abs(y - x) / np.maximum(np.abs(x), 1e-30))
        assert rel <= rel_bound, (codec.name, scale, rel)


@pytest.mark.parametrize("codec", [BF16, FP16, INT8])
def test_codec_empty_and_wire_bytes(codec):
    e = np.zeros(0, np.float32)
    enc = codec.encode(e)
    assert enc.nbytes == codec.wire_bytes(0)
    assert codec.decode(enc, 0).size == 0
    x = np.ones(33, np.float32)
    assert codec.encode(x).nbytes == codec.wire_bytes(33)


@pytest.mark.parametrize("codec", [BF16, FP16])
def test_fixed_width_special_values(codec):
    s = np.array([np.inf, -np.inf, np.nan, 0.0, -0.0, 1e-40],
                 np.float32)
    y = codec.decode(codec.encode(s), s.size)
    assert np.isposinf(y[0]) and np.isneginf(y[1])
    assert np.isnan(y[2])
    assert y[3] == 0.0 and y[4] == 0.0
    # fp32 denormal: representable (bf16 shares the fp32 exponent) or
    # flushed toward zero (fp16) — never inf/nan.
    assert np.isfinite(y[5])


@pytest.mark.parametrize("codec", [BF16, FP16])
def test_fixed_width_grid_idempotent(codec):
    """decode∘encode is a projection: applying it twice equals once.
    The ring allgather's owner-side projection and the lossless
    first-hop re-encode both rely on this."""
    x = np.random.default_rng(3).standard_normal(1024).astype(np.float32)
    g = codec.roundtrip(x)
    assert np.array_equal(g, codec.roundtrip(g))
    assert np.array_equal(codec.encode(g), codec.encode(g))


def test_bf16_fallback_bit_identical_to_ml_dtypes():
    if C._BF16_DTYPE is None:
        pytest.skip("ml_dtypes not available")
    x = np.random.default_rng(11).standard_normal(4096).astype(np.float32)
    x[:3] = [np.inf, -np.inf, np.nan]
    fast = BF16.encode(x).copy()
    try:
        C._BF16_DTYPE = None
        slow = BF16.encode(x)
        # NaN payloads may differ bit-wise; compare decoded semantics
        # elementwise and exact bits everywhere finite.
        yf = BF16.decode(fast, x.size)
    finally:
        C._BF16_DTYPE = np.dtype(__import__("ml_dtypes").bfloat16)
    ys = BF16.decode(slow, x.size)
    finite = np.isfinite(x)
    assert np.array_equal(yf[finite], ys[finite])
    assert np.isnan(ys[2]) and np.isnan(yf[2])


def test_int8_scale_and_edge_cases():
    x = np.array([-1.0, -0.5, 0.0, 0.25, 1.27], np.float32)
    y = INT8.decode(INT8.encode(x), x.size)
    scale = 1.27 / 127.0
    assert np.max(np.abs(y - x)) <= scale / 2 + 1e-7
    # all-zero -> zeros, zero scale
    z = np.zeros(16, np.float32)
    assert np.array_equal(INT8.decode(INT8.encode(z), 16), z)
    # non-finite-only input must not crash or poison the frame
    s = np.array([np.inf, -np.inf, np.nan], np.float32)
    out = INT8.decode(INT8.encode(s), 3)
    assert np.all(np.isfinite(out))
    # mixed: finite values set the scale, non-finite clip to extremes
    m = np.array([np.inf, 2.0, -np.inf, np.nan], np.float32)
    om = INT8.decode(INT8.encode(m), 4)
    assert om[0] == pytest.approx(2.0) and om[2] == pytest.approx(-2.0)
    assert om[3] == 0.0
    # denormals quantize to zero at any reasonable scale
    d = np.array([1e-40, 1.0], np.float32)
    od = INT8.decode(INT8.encode(d), 2)
    assert od[0] == 0.0


def test_codec_registry_lookup():
    assert C.codec_by_id(C.CODEC_BF16) is BF16
    assert C.codec_by_id(0) is None
    assert C.codec_by_id(999) is None  # unknown id degrades, not crash
    assert C.codec_by_name("nope") is None
    assert not BF16.applicable(np.float64)
    assert BF16.applicable(np.float32)


# ---------------------------------------------------------------------------
# error feedback

def _quadratic_descent(codec, use_ef, steps=300, lr=0.1):
    t = np.linspace(-3.0, 7.0, 256).astype(np.float32)
    x = np.zeros_like(t)
    res = np.zeros_like(t)
    for _ in range(steps):
        g = x - t
        if codec is not None:
            if use_ef:
                pre = g + res
                wire = codec.roundtrip(pre)
                res = pre - wire
                g = wire
            else:
                g = codec.roundtrip(g)
        x = x - lr * g
    return 0.5 * float(np.mean((x - t) ** 2)), float(np.max(np.abs(res)))


def test_error_feedback_fixes_quantized_descent():
    """EF-SGD on a quadratic (int8 — the coarsest codec): with error
    feedback the final loss matches uncompressed within tolerance and
    the residual stays bounded (the Karimireddy et al. 2019 claim).
    The engine-level mean-recovery test below covers the case where a
    single compressed round is provably off-grid."""
    plain, _ = _quadratic_descent(None, False)
    ef, res_max = _quadratic_descent(INT8, True)
    assert ef <= plain + 1e-6
    assert ef < 1e-4
    # residual bounded by one quantization step's worth of gradient
    assert res_max < 1.0


def test_error_feedback_survives_fp16_saturation():
    """fp16 saturates finite fp32 values past 65504 to inf; the
    residual (pre - inf = -inf) must reset to 0 instead of poisoning
    every later round into NaN (inf - inf). The round that overflowed
    still ships inf — the user sees it — but once gradients return to
    range, error feedback resumes cleanly."""
    ef = C.ErrorFeedback()
    big = np.array([1e6, 1.0], np.float32)  # element 0 overflows fp16
    pre = big.copy()
    wire = FP16.roundtrip(pre)
    assert np.isposinf(wire[0])
    ef.update("k", pre, wire)
    r = ef.get("k", 2)
    assert np.isfinite(r).all() and r[0] == 0.0
    # next round with a normal gradient: no NaN anywhere
    g = np.array([2.0, 3.0], np.float32)
    pre2 = g + r
    wire2 = FP16.roundtrip(pre2)
    ef.update("k", pre2, wire2)
    assert np.isfinite(wire2).all()
    assert np.isfinite(ef.get("k", 2)).all()
    # same defense on the fresh-allocation path
    ef2 = C.ErrorFeedback()
    ef2.update("fresh", pre, wire)
    assert np.isfinite(ef2.get("fresh", 2)).all()


def test_error_feedback_store_bounded():
    """A workload with uniquely-named allreduces must not leak one
    full-width residual per name forever: the store caps at its
    capacity, evicting the least recently updated."""
    ef = C.ErrorFeedback(capacity=4)
    for i in range(10):
        ef.update(f"t{i}", np.ones(4, np.float32),
                  np.zeros(4, np.float32))
    assert ef.size() == 4
    assert ef.get("t0", 4) is None      # oldest evicted
    assert ef.get("t9", 4) is not None  # newest kept
    # an update refreshes recency
    ef.update("t6", np.ones(4, np.float32), np.zeros(4, np.float32))
    ef.update("new", np.ones(4, np.float32), np.zeros(4, np.float32))
    assert ef.get("t6", 4) is not None
    assert C.ErrorFeedback().capacity == 1024


def test_error_feedback_store_lifecycle():
    ef = C.ErrorFeedback()
    assert ef.get("k", 4) is None
    r0 = np.ones(4, np.float32)
    ef.put("k", r0)
    assert ef.get("k", 4) is r0
    # size mismatch (renegotiated shape) drops rather than misapplies
    assert ef.get("k", 8) is None
    # update reuses the dead residual's buffer when shapes match
    pre = np.full(4, 2.0, np.float32)
    wire = np.full(4, 1.5, np.float32)
    ef.update("k", pre, wire)
    assert ef.get("k", 4) is r0  # same buffer, new contents
    assert np.allclose(r0, 0.5)
    assert ef.size() == 1 and ef.nbytes() == 16
    ef.reset()
    assert ef.size() == 0


# ---------------------------------------------------------------------------
# wire message + coordinator policy

def test_response_codec_rides_the_wire():
    resp = Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=["t"], tensor_shapes=[(8,)],
                    channel=1, codec=C.CODEC_FP16)
    r2, _ = Response.deserialize(resp.serialize())
    assert r2.codec == C.CODEC_FP16
    assert r2 == resp
    assert Response.deserialize(Response().serialize())[0].codec == 0


class _DummyTransport:
    rank = 0
    size = 2


def _controller():
    from horovod_tpu.engine.controller import Controller

    return Controller(_DummyTransport(), 2, 0,
                      registry=telemetry.MetricsRegistry())


def _resp(nelems=65536, dtype=DataType.FLOAT32, channel=0,
          rtype=ResponseType.ALLREDUCE, reduce_op=0):
    return Response(response_type=rtype, tensor_names=["g"],
                    tensor_shapes=[(nelems,)], tensor_type=dtype,
                    channel=channel, reduce_op=reduce_op)


def test_assign_codecs_policy(monkeypatch):
    ctrl = _controller()
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "bf16")
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "65536")
    big, small = _resp(65536), _resp(1024)
    not_f32 = _resp(65536, dtype=DataType.FLOAT64)
    maxred = _resp(65536, reduce_op=int(ReduceOp.MAX))
    gather = _resp(65536, rtype=ResponseType.ALLGATHER)
    ctrl._assign_codecs([big, small, not_f32, maxred, gather])
    assert big.codec == C.CODEC_BF16        # >= min_bytes
    assert small.codec == 0                 # below min_bytes
    assert not_f32.codec == 0               # fp32 only
    assert maxred.codec == 0                # SUM only
    assert gather.codec == 0                # allreduce only

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "fp16")
    r = _resp(65536)
    ctrl._assign_codecs([r])
    assert r.codec == C.CODEC_FP16

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "auto")
    r = _resp(65536)
    ctrl._assign_codecs([r])
    assert r.codec == C.CODEC_BF16          # auto = TPU-native bf16

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "none")
    r = _resp(1 << 24)
    ctrl._assign_codecs([r])
    assert r.codec == 0                     # none wins at any size


def test_assign_codecs_int8_latency_lane(monkeypatch):
    ctrl = _controller()
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "bf16")
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "0")
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_INT8", "1")
    monkeypatch.setenv("HOROVOD_NUM_CHANNELS", "2")
    lane = _resp(1024, channel=1)   # the latency lane (nchan-1)
    bulk = _resp(65536, channel=0)
    ctrl._assign_codecs([lane, bulk])
    assert lane.codec == C.CODEC_INT8
    assert bulk.codec == C.CODEC_BF16
    # int8 only for STAR-BOUND sizes: a ring/arena-eligible payload
    # would pay the coarse int8 projection while shipping full-width
    # (variable-width codecs can't be sliced by element offsets), so
    # with the ring threshold at 0 the lane falls back to the wide
    # codec instead.
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    lane_ring = _resp(1024, channel=1)
    ctrl._assign_codecs([lane_ring])
    assert lane_ring.codec == C.CODEC_BF16
    monkeypatch.delenv("HOROVOD_RING_THRESHOLD")
    # int8 stays opt-in: without the knob the lane follows size policy
    monkeypatch.delenv("HOROVOD_WIRE_COMPRESSION_INT8")
    lane2 = _resp(1024, channel=1)
    ctrl._assign_codecs([lane2])
    assert lane2.codec == C.CODEC_BF16


# ---------------------------------------------------------------------------
# compressed data planes (direct mixin use under an explicit scope)

def _run_pair(fn):
    from horovod_tpu.backend.transport import make_inproc_backends

    backends = make_inproc_backends(2)
    results = [None, None]
    errors = [None, None]

    def worker(r):
        try:
            results[r] = fn(backends[r], r)
        except BaseException as ex:  # noqa: BLE001
            errors[r] = ex

    threads = [threading.Thread(target=worker, args=(r,)) for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for b in backends:
        b.shutdown()
    return results, errors


def test_compressed_ring_allreduce_bitwise_agreement(monkeypatch):
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "0")

    def fn(b, r):
        x = np.full(1000, (r + 1) / 3.0, np.float32)
        with channel_scope(1), wire_codec_scope(BF16):
            return b.allreduce(x, ReduceOp.SUM)

    (a, bb), errors = _run_pair(fn)
    assert not any(errors), errors
    assert np.array_equal(a, bb), "ranks diverged under compression"
    assert abs(float(a[0]) - 1.0) < 0.01


def test_compressed_segmented_ring(monkeypatch):
    """Segment bounds stay in element space, so a segmented compressed
    ring's frame sizes agree ((b-a) * wire_itemsize on both sides)."""
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "256")

    def fn(b, r):
        x = np.full(1000, float(r + 1), np.float32)
        with channel_scope(1), wire_codec_scope(FP16):
            return b.allreduce(x, ReduceOp.SUM)

    (a, bb), errors = _run_pair(fn)
    assert not any(errors), errors
    assert np.array_equal(a, bb)
    assert float(a[0]) == 3.0  # exact in fp16


def test_compressed_star_allreduce(monkeypatch):
    monkeypatch.setenv("HOROVOD_CPU_OPERATIONS", "star")
    stats = C.CompressionStats(telemetry.MetricsRegistry())

    def fn(b, r):
        x = np.full(64, (r + 1) * 0.5, np.float32)
        with wire_codec_scope(INT8, stats):
            return b.allreduce(x, ReduceOp.SUM)

    (a, bb), errors = _run_pair(fn)
    assert not any(errors), errors
    assert np.array_equal(a, bb)
    assert abs(float(a[0]) - 1.5) < 1.5 / 127 + 1e-6
    saved = stats.saved_snapshot()
    # worker gather frame + root bcast: both counted, exactly
    assert saved.get("int8") == 2 * (64 * 4 - (64 + 4))


def test_uncompressed_scope_is_inert():
    assert current_wire_codec() is None

    def fn(b, r):
        x = np.full(64, float(r + 1), np.float32)
        return b.allreduce(x, ReduceOp.SUM)

    (a, bb), errors = _run_pair(fn)
    assert not any(errors), errors
    assert float(a[0]) == 3.0 and np.array_equal(a, bb)


def test_arena_compressed_deposits(tmp_path):
    from horovod_tpu.backend.shm import ShmArena

    path = str(tmp_path / "arena")
    arenas = [ShmArena(path, i, 2, 1 << 16) for i in range(2)]
    inputs = [np.full(5000, (i + 1) / 3.0, np.float32) for i in range(2)]
    outs = [np.empty_like(inputs[i]) for i in range(2)]
    stats = C.CompressionStats(telemetry.MetricsRegistry())
    errors = [None, None]

    def worker(i):
        try:
            arenas[i].allreduce_into(
                inputs[i], lambda d, s: np.add(d, s, out=d),
                out=outs[i], codec=BF16, stats=stats)
        except BaseException as ex:  # noqa: BLE001
            errors[i] = ex

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(errors), errors
    # the shared result is computed once per subslice: bitwise equal
    assert np.array_equal(outs[0], outs[1])
    expect = BF16.roundtrip(inputs[0]) + BF16.roundtrip(inputs[1])
    assert np.allclose(outs[0], expect, rtol=0, atol=0)
    # deposits streamed in >=1 chunk; every chunk saved half its bytes
    assert stats.saved_snapshot()["bf16"] == 2 * inputs[0].nbytes // 2
    for a in arenas:
        a.close()


# ---------------------------------------------------------------------------
# zero-redundancy first hop + pipelined codec/wire overlap


def test_first_hop_reuse_bitwise_identical(monkeypatch):
    """A star allreduce fed the engine's pre-encoded first-hop bytes
    finishes bitwise identical to one that re-encodes — encode is
    value-deterministic, so shipping the stash IS shipping the
    re-encode."""
    monkeypatch.setenv("HOROVOD_CPU_OPERATIONS", "star")
    rng = np.random.RandomState(7)
    xs = [rng.rand(777).astype(np.float32) for _ in range(2)]

    def run(reuse):
        def fn(b, r):
            stash = BF16.encode(xs[r]) if reuse else None
            with wire_codec_scope(BF16, first_hop=stash):
                out = b.allreduce(xs[r].copy(), ReduceOp.SUM)
                if reuse:
                    # consume-once: the data plane took it.
                    from horovod_tpu.backend.base import (
                        take_first_hop_encoded,
                    )

                    assert take_first_hop_encoded(stash.nbytes) is None
                return out
        (a, bb), errors = _run_pair(fn)
        assert not any(errors), errors
        assert np.array_equal(a, bb)
        return a

    np.testing.assert_array_equal(run(True), run(False))


def test_first_hop_stash_size_mismatch_is_ignored():
    """Defense in depth: a stash whose byte size does not match the
    buffer being shipped is dropped, never sliced wrong."""
    from horovod_tpu.backend.base import take_first_hop_encoded

    x = np.ones(64, np.float32)
    with wire_codec_scope(BF16, first_hop=BF16.encode(x)):
        assert take_first_hop_encoded(999) is None
        # consumed by the failed take: a second take sees nothing.
        assert take_first_hop_encoded(128) is None


def test_engine_first_hop_single_encode_count_star():
    """Acceptance proof (ISSUE 14): exactly ONE encode pass per
    compressed op on the first hop. Every encode site observes into
    horovod_compression_seconds{phase="encode"}, so the observation
    COUNT is the pass count: on the star path a worker pays only the
    engine's error-feedback encode (1/op — the gather ships the stash),
    and the root pays the engine's plus its result-broadcast re-encode
    (2/op). A re-encoding first hop would read 2/op on the worker."""
    iters = 4
    regs = [telemetry.MetricsRegistry() for _ in range(2)]

    def fn(eng, r):
        outs = []
        for i in range(iters):
            h = eng.enqueue_allreduce(
                np.full(300, float(r + 1), np.float32), name="t")
            outs.append(eng.synchronize(h, timeout=30))
        return outs

    results, engines, regs = _run_engines(
        2, fn, dict(_CMP_ENV, HOROVOD_CPU_OPERATIONS="star"),
        registries=regs)
    key = 'horovod_compression_seconds{phase="encode"}_count'
    assert regs[0].scalars().get(key, 0) == 2 * iters  # root
    assert regs[1].scalars().get(key, 0) == 1 * iters  # worker
    np.testing.assert_array_equal(results[0][0], results[1][0])


def test_engine_first_hop_single_encode_count_ring(monkeypatch):
    """Ring closed form at np=2: the engine's EF encode (1) + the
    allgather owner projection (1, whose bytes step 0 ships — the old
    separate step-0 re-encode is gone) = exactly 2/op per rank; the
    reduce-scatter's only step ships the engine's stash."""
    iters = 3
    regs = [telemetry.MetricsRegistry() for _ in range(2)]

    def fn(eng, r):
        outs = []
        for i in range(iters):
            h = eng.enqueue_allreduce(
                np.full(5000, float(r + 1), np.float32), name="t")
            outs.append(eng.synchronize(h, timeout=30))
        return outs

    results, engines, regs = _run_engines(
        2, fn, dict(_CMP_ENV, HOROVOD_RING_THRESHOLD="0",
                    HOROVOD_RING_SEGMENT_BYTES="0"),
        registries=regs)
    key = 'horovod_compression_seconds{phase="encode"}_count'
    for r in (0, 1):
        assert regs[r].scalars().get(key, 0) == 2 * iters, (
            r, regs[r].scalars().get(key, 0))
    np.testing.assert_array_equal(results[0][0], results[1][0])


def test_arena_first_hop_deposit(tmp_path):
    """The whole-world arena's encoded deposits slice the engine's
    first-hop bytes: zero encode observations, exact byte-savings
    accounting, full-width results bitwise identical to the
    recomputed-encode run, and deposit/copy-out conservation (sent =
    encoded bytes, recv = full-width bytes)."""
    from horovod_tpu.backend.shm import ShmArena

    inputs = [np.full(5000, (i + 1) / 3.0, np.float32)
              for i in range(2)]
    expect = BF16.roundtrip(inputs[0]) + BF16.roundtrip(inputs[1])

    def run(reuse, tag):
        arenas = [ShmArena(str(tmp_path / tag), i, 2, 1 << 16)
                  for i in range(2)]
        reg = telemetry.MetricsRegistry()
        sent = reg.counter("sent", "")
        recv = reg.counter("recv", "")
        for a in arenas:
            a.m_sent, a.m_recv = sent, recv
        stats = C.CompressionStats(telemetry.MetricsRegistry())
        outs = [np.empty_like(inputs[i]) for i in range(2)]
        errors = [None, None]

        def worker(i):
            try:
                fh = BF16.encode(inputs[i]) if reuse else None
                arenas[i].allreduce_into(
                    inputs[i], lambda d, s: np.add(d, s, out=d),
                    out=outs[i], codec=BF16, stats=stats, first_hop=fh)
            except BaseException as ex:  # noqa: BLE001
                errors[i] = ex

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(errors), errors
        for a in arenas:
            a.close()
        return outs, stats, sent.value, recv.value

    outs, stats, sent, recv = run(True, "a1")
    assert np.array_equal(outs[0], outs[1])
    np.testing.assert_allclose(outs[0], expect, rtol=0, atol=0)
    # no encode pass ran in the arena; savings still counted exactly
    snap = stats._seconds
    assert "encode" not in snap
    assert stats.saved_snapshot()["bf16"] == 2 * inputs[0].nbytes // 2
    # conservation: sent counts encoded deposits, recv full-width outs
    assert sent == 2 * inputs[0].nbytes // 2
    assert recv == 2 * inputs[0].nbytes
    outs2, stats2, _, _ = run(False, "a2")
    np.testing.assert_array_equal(outs[0], outs2[0])
    assert "encode" in stats2._seconds  # the recompute arm DID encode


@pytest.mark.parametrize("nranks", [2, 3, 4])
def test_pipelined_ring_bitwise_vs_serial(nranks, monkeypatch):
    """HOROVOD_RING_CODEC_OVERLAP moves codec passes onto bounded
    worker stages without changing a single wire byte: results are
    bitwise identical to the serial schedule and bitwise identical
    across ranks, segments and remainders included."""
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "256")

    def run(nr, overlap):
        monkeypatch.setenv("HOROVOD_RING_CODEC_OVERLAP",
                           "1" if overlap else "0")
        from horovod_tpu.backend.transport import make_inproc_backends

        backends = make_inproc_backends(nr)
        results = [None] * nr
        errors = [None] * nr

        def worker(r):
            try:
                rng = np.random.RandomState(r)
                x = rng.rand(5003).astype(np.float32)
                with channel_scope(1), wire_codec_scope(BF16):
                    results[r] = backends[r].allreduce(x, ReduceOp.SUM)
            except BaseException as e:  # noqa: BLE001
                errors[r] = e

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(nr)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for b in backends:
            b.shutdown()
        assert not any(errors), errors
        return results

    serial = run(nranks, False)
    over = run(nranks, True)
    for r in range(nranks):
        assert np.array_equal(serial[0], serial[r])
        assert np.array_equal(over[0], over[r])
    assert np.array_equal(serial[0], over[0])


def test_pipeline_stage_fifo_and_error_propagation():
    """The bounded single-worker stage runs jobs strictly FIFO and
    parks a job's exception in its future (later jobs still run)."""
    from horovod_tpu.common.compression import PipelineStage

    seen = []
    with PipelineStage("t", depth=2) as stage:
        futs = [stage.submit(lambda i=i: seen.append(i) or i)
                for i in range(8)]
        assert [f.result() for f in futs] == list(range(8))
        assert seen == list(range(8))

        def boom():
            raise ValueError("job failed")

        bad = stage.submit(boom)
        good = stage.submit(lambda: "after")
        with pytest.raises(ValueError, match="job failed"):
            bad.result()
        assert good.result() == "after"


def test_ring_codec_overlap_parse(monkeypatch):
    from horovod_tpu.utils import env as env_cfg

    monkeypatch.delenv("HOROVOD_RING_CODEC_OVERLAP", raising=False)
    assert env_cfg.ring_codec_overlap() is True
    monkeypatch.setenv("HOROVOD_RING_CODEC_OVERLAP", "0")
    assert env_cfg.ring_codec_overlap() is False
    monkeypatch.setenv("HOROVOD_RING_CODEC_OVERLAP", "1")
    assert env_cfg.ring_codec_overlap() is True


# ---------------------------------------------------------------------------
# engine integration: negotiated codec, cache replay, residuals

def _run_engines(size, fn, env, registries=None):
    from horovod_tpu.backend.threaded import ThreadedGroup
    from horovod_tpu.engine.engine import Engine

    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        group = ThreadedGroup(size)
        regs = registries or [telemetry.MetricsRegistry()
                              for _ in range(size)]
        engines = [Engine(rank=r, size=size, backend=group.backend(r),
                          registry=regs[r]) for r in range(size)]
        for e in engines:
            e.cycle_time_s = 0.001
            e.start()
        results = [None] * size
        errors = [None] * size

        def worker(r):
            try:
                results[r] = fn(engines[r], r)
            except BaseException as ex:  # noqa: BLE001
                errors[r] = ex

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(size)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop = [threading.Thread(target=e.shutdown) for e in engines]
        for t in stop:
            t.start()
        for t in stop:
            t.join(timeout=60)
        for err in errors:
            if err is not None:
                raise err
        return results, engines, regs
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_CMP_ENV = {
    "HOROVOD_WIRE_COMPRESSION": "bf16",
    "HOROVOD_WIRE_COMPRESSION_MIN_BYTES": "0",
}


def test_engine_negotiated_compression_and_cache_replay():
    """The coordinator assigns bf16, the codec id rides the wire, and
    cache-replayed cycles keep compressing (bytes-saved keeps growing
    after the first negotiation) with bitwise cross-rank agreement."""
    iters = 4

    def fn(eng, rank):
        outs = []
        for _ in range(iters):  # steady name -> cache replay after #1
            x = np.full(512, (rank + 1) * 0.1, np.float32)
            outs.append(eng.synchronize(
                eng.enqueue_allreduce(x, name="cmp"), timeout=30))
        return outs

    results, engines, regs = _run_engines(2, fn, _CMP_ENV)
    for o0, o1 in zip(results[0], results[1]):
        assert np.array_equal(o0, o1)
        assert abs(float(o0[0]) - 0.3) < 0.01
    for reg in regs:
        saved = reg.snapshot().get(
            'horovod_wire_bytes_saved_total{codec="bf16"}', 0)
        # every iteration compressed: star worker/bcast frames save
        # 512 * 2 bytes each, once per iteration on each rank
        assert saved == iters * 512 * 2, saved
    # per-(tensor-name) residual exists on both ranks
    for eng in engines:
        assert eng._error_feedback.size() == 1


def test_engine_error_feedback_recovers_mean():
    """1/3 is not bf16-representable; with error feedback the
    time-average of compressed allreduce results converges to the true
    sum (the EF guarantee), while any single round is off-grid."""
    iters = 50
    true = 2.0 / 3.0  # (1/3) * 2 ranks... per-rank value 1/3

    def fn(eng, rank):
        acc = 0.0
        for _ in range(iters):
            x = np.full(8, 1.0 / 3.0, np.float32)
            out = eng.synchronize(
                eng.enqueue_allreduce(x, name="ef"), timeout=30)
            acc += float(np.asarray(out)[0])
        return acc / iters

    results, engines, _ = _run_engines(2, fn, _CMP_ENV)
    for mean in results:
        assert abs(mean - true) < 1e-4, mean


def test_engine_residuals_reset_with_engine_lifecycle():
    """An elastic reset builds a fresh Engine on every rank; residuals
    are engine-owned, so the reset zeroes them consistently."""

    def fn(eng, rank):
        x = np.full(16, 1.0 / 3.0, np.float32)
        eng.synchronize(eng.enqueue_allreduce(x, name="r"), timeout=30)
        return eng._error_feedback.size()

    results, engines, _ = _run_engines(2, fn, _CMP_ENV)
    assert results == [1, 1]
    # the "reset": a new engine pair starts with zero residuals
    def probe(eng, rank):
        return eng._error_feedback.size()

    results2, _, _ = _run_engines(2, probe, _CMP_ENV)
    assert results2 == [0, 0]


def test_engine_join_under_compression():
    """A joined rank must enter the compressed collective with encoded
    zero frames — full-width frames from the joined rank would desync
    the stream (frame sizes are codec-derived)."""

    def fn(eng, rank):
        if rank == 1:
            return eng.synchronize(eng.enqueue_join(), timeout=30)
        x = np.full(512, 2.0, np.float32)
        out = eng.synchronize(
            eng.enqueue_allreduce(x, name="j"), timeout=30)
        eng.synchronize(eng.enqueue_join(), timeout=30)
        return out

    results, _, _ = _run_engines(2, fn, _CMP_ENV)
    assert float(np.asarray(results[0])[0]) == 2.0  # zeros joined in


def test_engine_status_has_wire_compression_row():
    def fn(eng, rank):
        x = np.full(512, 1.0, np.float32)
        eng.synchronize(eng.enqueue_allreduce(x, name="s"), timeout=30)
        return eng.status()["wire_compression"]

    results, _, _ = _run_engines(2, fn, _CMP_ENV)
    row = results[0]
    assert row["mode"] == "bf16"
    assert row["residual_tensors"] == 1
    assert row["bytes_saved"].get("bf16", 0) > 0


def test_engine_training_loss_parity_bf16_vs_none():
    """Accuracy-parity check through the REAL engine data plane: a
    2-rank data-parallel least-squares model trained with gradient
    allreduce under bf16+error-feedback must reach the same final loss
    as the uncompressed run within noise (the benchmark's cells ride the
    traced/XLA path, which the wire codec never touches — this loop is
    the eager engine's equivalent)."""
    rng = np.random.default_rng(5)
    w_true = rng.standard_normal(8).astype(np.float32)
    data = [rng.standard_normal((64, 8)).astype(np.float32)
            for _ in range(2)]
    targets = [d @ w_true for d in data]

    def train(env):
        def fn(eng, rank):
            w = np.zeros(8, np.float32)
            X, y = data[rank], targets[rank]
            for _ in range(100):
                pred = X @ w
                grad = (X.T @ (pred - y)) / len(y)
                g = np.asarray(eng.synchronize(
                    eng.enqueue_allreduce(grad, name="g",
                                          op=ReduceOp.AVERAGE),
                    timeout=30))
                w = w - 0.4 * g
            resid = np.concatenate([Xr @ w - yr
                                    for Xr, yr in zip(data, targets)])
            return float(np.mean(resid ** 2))

        results, _, _ = _run_engines(2, fn, env)
        assert results[0] == pytest.approx(results[1])
        return results[0]

    loss_cmp = train(_CMP_ENV)
    loss_none = train({"HOROVOD_WIRE_COMPRESSION": "none"})
    assert loss_none < 1e-3
    # parity within noise: compressed-with-EF tracks uncompressed
    assert loss_cmp < max(2 * loss_none, 1e-3)


# ---------------------------------------------------------------------------
# codec-mismatch desync attribution over real TCP

def test_codec_mismatch_desyncs_with_attribution(monkeypatch):
    """One rank ring-reduces compressed, the other full-width: the
    half-width frame meets the full-width recv_into and every involved
    rank fails with the single-source desync message naming BOTH knobs
    that change frame sizes — never a hang, never a raw socket error."""
    from test_fault_tolerance import _tcp_pair

    monkeypatch.setenv("HOROVOD_TCP_TIMEOUT_SECONDS", "10")
    monkeypatch.setenv("HOROVOD_RING_THRESHOLD", "0")
    monkeypatch.setenv("HOROVOD_RING_SEGMENT_BYTES", "0")
    server, (b0, b1) = _tcp_pair("t_codec_desync", monkeypatch)
    errors = [None, None]

    def worker(r, backend, codec):
        x = np.full(1000, float(r + 1), np.float32)
        try:
            with channel_scope(1), wire_codec_scope(codec):
                backend.allreduce(x, ReduceOp.SUM)
        except HorovodInternalError as ex:
            errors[r] = ex

    threads = [
        threading.Thread(target=worker, args=(0, b0, BF16)),
        threading.Thread(target=worker, args=(1, b1, None)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    try:
        assert not any(t.is_alive() for t in threads), "desync hung"
        # Both ranks fail promptly; WHICHEVER side reads the
        # mismatched frame first raises the attributed single-source
        # message (the other sees its peer's sever as a transport
        # death — still an attributed TransportError, never a hang).
        assert errors[0] is not None and errors[1] is not None
        msgs = [str(e) for e in errors]
        attributed = [m for m in msgs if "desynced peer" in m]
        assert attributed, msgs
        for m in attributed:
            assert "HOROVOD_WIRE_COMPRESSION" in m
            assert "HOROVOD_RING_SEGMENT_BYTES" in m
        for e in errors:
            assert isinstance(e, (TransportError, HorovodInternalError))
    finally:
        b0.shutdown()
        b1.shutdown()
        server.stop()


# ---------------------------------------------------------------------------
# env knobs (house convention: parse tests incl. alias + bogus values)

def test_wire_compression_env_knobs(monkeypatch):
    from horovod_tpu.utils import env as env_cfg

    for k in ("HOROVOD_WIRE_COMPRESSION",
              "HOROVOD_WIRE_COMPRESSION_MIN_BYTES",
              "HOROVOD_WIRE_COMPRESSION_INT8"):
        monkeypatch.delenv(k, raising=False)
        monkeypatch.delenv(k.replace("HOROVOD_", "HVD_TPU_", 1),
                           raising=False)
    assert env_cfg.wire_compression_mode() == "none"
    assert env_cfg.wire_compression_min_bytes() == 65536
    assert env_cfg.wire_compression_int8() is False

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "BF16")
    assert env_cfg.wire_compression_mode() == "bf16"
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "bogus")
    assert env_cfg.wire_compression_mode() == "none"  # typo != surprise
    monkeypatch.delenv("HOROVOD_WIRE_COMPRESSION")
    monkeypatch.setenv("HVD_TPU_WIRE_COMPRESSION", "fp16")
    assert env_cfg.wire_compression_mode() == "fp16"

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "-5")
    assert env_cfg.wire_compression_min_bytes() == 0  # floored
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "1024")
    assert env_cfg.wire_compression_min_bytes() == 1024

    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_INT8", "1")
    assert env_cfg.wire_compression_int8() is True
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_INT8", "off")
    assert env_cfg.wire_compression_int8() is False


# ---------------------------------------------------------------------------
# namespace dedupe: one core, thin framework re-exports

def test_compression_namespaces_share_the_core():
    from horovod_tpu.ops import compression as jax_comp

    assert jax_comp.Compressor is C.Compressor
    assert jax_comp.NoneCompressor is C.NoneCompressor
    assert jax_comp.Compression.none is C.NoneCompressor
    # adapters stay framework-local but subclass the shared interface
    assert issubclass(jax_comp.BF16Compressor, C.Compressor)
    t, ctx = jax_comp.Compression.none.compress("x")
    assert t == "x" and ctx is None
