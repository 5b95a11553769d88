// Native core for the eager engine's host-side hot paths.
//
// The reference implements its whole runtime in C++ (horovod/common/ —
// operations.cc, collective_operations.cc fusion memcpys, adasum/adasum.h
// VHDD math). On TPU the *device* hot path is XLA; what remains hot on
// the host in process mode is exactly what lives here:
//
//   * per-segment in-place reduce for the ring's recv+reduce step
//     (ref: CPU allreduce inner loops, collective_operations.h:89-125)
//   * fused strided gather-reduce over the shm arena's deposit slots —
//     one pass over all peers instead of per-peer numpy adds
//   * k-way reduction kernels for the star data plane
//   * wire-codec passes: bf16/fp16/int8-with-scale encode/decode and
//     the error-feedback residual update, bit-compatible with the
//     numpy fallbacks in common/compression.py (rank-consistency
//     requires every host to produce the same wire bytes regardless
//     of whether it runs native or fallback)
//   * fusion-buffer pack/unpack, multithreaded memcpy
//     (ref: MemcpyInFusionBuffer/MemcpyOutFusionBuffer)
//   * the Adasum pairwise recursion with float64 dot/norm accumulation
//     (ref: ops/adasum/adasum.h:100-280)
//   * bit-vector AND/OR for cache coordination
//     (ref: response_cache.h bitvector sync)
//
// Exposed as a plain C ABI consumed via ctypes (horovod_tpu/cc/native.py)
// — the same load pattern as the reference's HorovodBasics
// (horovod/common/basics.py:22-233), no pybind dependency. ctypes
// releases the GIL for the duration of every call, so segment k's
// reduce genuinely overlaps segment k+1's recv across engine threads.
//
// Threading: one persistent worker pool (lazy, HOROVOD_NATIVE_THREADS,
// re-created after fork) instead of per-call std::thread spawns; on a
// single-core host the pool has zero workers and every kernel runs
// inline on the calling thread — still GIL-free.
//
// Build: `make -C horovod_tpu/cc` (g++ -O3 -shared; no external deps).

#include <atomic>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <mutex>
#include <thread>
#include <vector>

#include <stdlib.h>
#include <unistd.h>

namespace {

constexpr int64_t kParallelThresholdBytes = 1 << 20;  // 1 MB
constexpr int64_t kGrainElems = 1 << 16;

int hardware_threads() {
  unsigned n = std::thread::hardware_concurrency();
  return n == 0 ? 2 : static_cast<int>(n);
}

int configured_threads() {
  const char* env = getenv("HOROVOD_NATIVE_THREADS");
  if (env != nullptr && env[0] != '\0') {
    int v = atoi(env);
    if (v >= 1) return v > 64 ? 64 : v;
  }
  int hw = hardware_threads();
  return hw > 8 ? 8 : hw;  // memory-bound kernels saturate early
}

// Persistent worker pool. Callers hand it a chunk-indexed job; workers
// and the caller grab chunks from a shared atomic counter. try_run is
// non-blocking for concurrent callers: if another thread owns the pool
// (or the pool has no workers), the caller runs its job inline —
// graceful degradation instead of cross-channel serialization.
class Pool {
 public:
  explicit Pool(int workers) {
    for (int i = 0; i < workers; ++i)
      threads_.emplace_back([this] { worker_loop(); });
  }
  // Only ever runs on a pool that lost the creation race in pool() and
  // was handed no job: its idle workers must be joined, because
  // destroying a joinable std::thread calls std::terminate.
  ~Pool() {
    {
      std::lock_guard<std::mutex> lk(mu_);
      stop_ = true;
      ++epoch_;
      cv_.notify_all();
    }
    for (auto& t : threads_) t.join();
  }
  int workers() const { return static_cast<int>(threads_.size()); }

  bool try_run(int nchunks, const std::function<void(int)>& fn) {
    if (threads_.empty() || nchunks <= 0) return false;
    if (!run_mu_.try_lock()) return false;
    {
      std::unique_lock<std::mutex> lk(mu_);
      // Wait out stragglers from the previous epoch before resetting
      // the shared job state they may still be reading.
      idle_cv_.wait(lk, [this] { return active_ == 0; });
      job_ = &fn;
      nchunks_ = nchunks;
      next_.store(0, std::memory_order_relaxed);
      pending_.store(nchunks, std::memory_order_relaxed);
      ++epoch_;
      cv_.notify_all();
    }
    work();
    {
      std::unique_lock<std::mutex> lk(mu_);
      done_cv_.wait(lk, [this] { return pending_.load() == 0; });
      job_ = nullptr;
    }
    run_mu_.unlock();
    return true;
  }

 private:
  void work() {
    int i;
    while ((i = next_.fetch_add(1)) < nchunks_) {
      (*job_)(i);
      if (pending_.fetch_sub(1) == 1) {
        std::lock_guard<std::mutex> lk(mu_);
        done_cv_.notify_all();
      }
    }
  }
  void worker_loop() {
    uint64_t seen = 0;
    for (;;) {
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_.wait(lk, [&] { return epoch_ != seen; });
        if (stop_) return;
        seen = epoch_;
        ++active_;
      }
      work();
      {
        std::lock_guard<std::mutex> lk(mu_);
        --active_;
        if (active_ == 0) idle_cv_.notify_all();
      }
    }
  }

  std::mutex run_mu_;  // one job at a time; losers run inline
  std::mutex mu_;
  std::condition_variable cv_, done_cv_, idle_cv_;
  const std::function<void(int)>* job_ = nullptr;
  std::atomic<int> next_{0};
  std::atomic<int> pending_{0};
  int nchunks_ = 0;
  int active_ = 0;
  bool stop_ = false;
  uint64_t epoch_ = 0;
  std::vector<std::thread> threads_;
};

// Lock-free singleton keyed by pid: a fork (Python multiprocessing)
// leaves the parent's workers behind, so the child lazily builds a
// fresh pool. The stale pool leaks — its mutexes may have been copied
// mid-acquire, so it is never touched again.
std::atomic<Pool*> g_pool{nullptr};
std::atomic<long> g_pool_pid{0};

Pool* pool() {
  long pid = static_cast<long>(getpid());
  Pool* p = g_pool.load(std::memory_order_acquire);
  if (p != nullptr && g_pool_pid.load(std::memory_order_acquire) == pid)
    return p;
  Pool* fresh = new Pool(configured_threads() - 1);
  Pool* expected = p;
  if (g_pool.compare_exchange_strong(expected, fresh,
                                     std::memory_order_acq_rel)) {
    g_pool_pid.store(pid, std::memory_order_release);
    return fresh;
  }
  delete fresh;  // lost the race before any worker had work
  return g_pool.load(std::memory_order_acquire);
}

// Run fn(begin, end) over [0, n) in roughly equal chunks.
template <typename F>
void parallel_for(int64_t n, int64_t grain, const F& fn) {
  if (n <= 0) return;
  Pool* p = pool();
  int nthreads = (p != nullptr ? p->workers() : 0) + 1;
  int64_t chunks = (n + grain - 1) / grain;
  if (chunks > nthreads) chunks = nthreads;
  if (chunks <= 1 || p == nullptr || p->workers() == 0) {
    fn(0, n);
    return;
  }
  int64_t per = (n + chunks - 1) / chunks;
  std::function<void(int)> job = [&](int c) {
    int64_t b = c * per, e = std::min<int64_t>(n, b + per);
    if (b < e) fn(b, e);
  };
  if (!p->try_run(static_cast<int>(chunks), job)) fn(0, n);
}

// ---------------------------------------------------------------------------
// IEEE conversions, bit-exact vs the numpy fallbacks. The data plane's
// rank-consistency contract needs native and fallback hosts to emit
// identical wire bytes, so these mirror numpy's halffloat.c and the
// compression.py bf16 bit path operation for operation.

inline float bits_to_float(uint32_t u) {
  float f;
  std::memcpy(&f, &u, 4);
  return f;
}

inline uint32_t float_to_bits(float f) {
  uint32_t u;
  std::memcpy(&u, &f, 4);
  return u;
}

inline float bf16_to_float(uint16_t b) {
  return bits_to_float(static_cast<uint32_t>(b) << 16);
}

inline uint16_t float_to_bf16(float f) {
  uint32_t u = float_to_bits(f);
  // NaN: canonical quiet NaN, exactly like the ml_dtypes cast the
  // numpy fallback uses (payload dropped). inf needs no special case:
  // its mantissa is zero so the RNE add cannot carry into the
  // exponent and truncation falls out of the shift. One select keeps
  // the loop branchless, which is what lets the SIMD clones vectorize
  // it (ml_dtypes' Eigen cast is vectorized; matching its speed
  // requires matching its shape).
  uint32_t lsb = (u >> 16) & 1u;
  uint16_t r = static_cast<uint16_t>((u + 0x7FFFu + lsb) >> 16);
  uint16_t canon = (u & 0x80000000u) != 0 ? 0xFFC0u : 0x7FC0u;
  return (u & 0x7FFFFFFFu) > 0x7F800000u ? canon : r;
}

inline float half_to_float(uint16_t h) {
  uint32_t sign = static_cast<uint32_t>(h & 0x8000u) << 16;
  uint32_t exp = (h >> 10) & 0x1Fu;
  uint32_t man = h & 0x3FFu;
  if (exp == 0) {
    if (man == 0) return bits_to_float(sign);
    int shift = 0;
    while ((man & 0x400u) == 0) {
      man <<= 1;
      ++shift;
    }
    man &= 0x3FFu;
    return bits_to_float(
        sign | (static_cast<uint32_t>(113 - shift) << 23) | (man << 13));
  }
  if (exp == 31) return bits_to_float(sign | 0x7F800000u | (man << 13));
  return bits_to_float(sign | ((exp + 112u) << 23) | (man << 13));
}

// Runtime SIMD dispatch (docs/native.md): the .so must run on any
// x86-64 host, so instead of -march=native the hot loops are compiled
// once per ISA (baseline SSE2 / AVX2 / AVX-512) and glibc's ifunc
// resolver picks the widest the CPU supports at load time. Every
// clone performs the same IEEE operations in the same order — wider
// registers only — so results stay bitwise identical across hosts,
// which the rank-consistency contract requires.
#if defined(__x86_64__) && defined(__GNUC__) && !defined(__clang__)
#define HVD_SIMD_CLONES \
  __attribute__((target_clones("default", "avx2", "avx512f")))
#else
#define HVD_SIMD_CLONES
#endif

inline uint16_t float_to_half(float f) {
  uint32_t u = float_to_bits(f);
  uint16_t sign = static_cast<uint16_t>((u >> 16) & 0x8000u);
  uint32_t x = u & 0x7FFFFFFFu;
  if (x >= 0x7F800000u) {  // inf / NaN
    if (x == 0x7F800000u) return sign | 0x7C00u;
    uint16_t sig = static_cast<uint16_t>((x & 0x007FFFFFu) >> 13);
    if (sig == 0) sig = 1;  // keep NaN a NaN after truncation
    return static_cast<uint16_t>(sign | 0x7C00u | sig);
  }
  if (x >= 0x477FF000u) return sign | 0x7C00u;  // rounds past max finite
  if (x >= 0x38800000u) {                       // normal half
    uint32_t lsb = (x >> 13) & 1u;
    x += 0xFFFu + lsb;
    return static_cast<uint16_t>(sign | ((x - 0x38000000u) >> 13));
  }
  if (x <= 0x33000000u) return sign;  // underflow (tie at 2^-25 -> even)
  // subnormal half: round man * 2^(e-150) to multiples of 2^-24
  uint32_t e = x >> 23;
  uint32_t man = (x & 0x007FFFFFu) | 0x00800000u;
  int shift = 126 - static_cast<int>(e);  // 14..24 in this range
  uint32_t shifted = man >> shift;
  uint32_t rem = man & ((1u << shift) - 1u);
  uint32_t half = 1u << (shift - 1);
  if (rem > half || (rem == half && (shifted & 1u))) ++shifted;
  return static_cast<uint16_t>(sign | shifted);
}

// ---------------------------------------------------------------------------
// dtype traits: S = storage element, C = compute type. Reduced floats
// compute in f32 with a round-to-storage per op — exactly numpy's
// float16/bfloat16 ufunc semantics, so native and fallback agree
// bitwise.

template <typename T>
struct Plain {
  using S = T;
  using C = T;
  static inline C ld(S v) { return v; }
  static inline S st(C v) { return v; }
};

struct Half {
  using S = uint16_t;
  using C = float;
  static inline C ld(S v) { return half_to_float(v); }
  static inline S st(C v) { return float_to_half(v); }
};

struct Bf16 {
  using S = uint16_t;
  using C = float;
  static inline C ld(S v) { return bf16_to_float(v); }
  static inline S st(C v) { return float_to_bf16(v); }
};

// op: 0=sum, 1=min, 2=max, 3=prod. min/max comparison semantics match
// the pre-existing f32 kernel (first operand wins on NaN), used on
// finite data by every caller.
template <typename TR>
inline void reduce_into_range(typename TR::S* tgt, const typename TR::S* src,
                              int64_t b, int64_t e, int op) {
  switch (op) {
    case 0:
      for (int64_t i = b; i < e; ++i)
        tgt[i] = TR::st(TR::ld(tgt[i]) + TR::ld(src[i]));
      break;
    case 1:
      for (int64_t i = b; i < e; ++i) {
        auto s = TR::ld(src[i]);
        auto t = TR::ld(tgt[i]);
        tgt[i] = TR::st(s < t ? s : t);
      }
      break;
    case 2:
      for (int64_t i = b; i < e; ++i) {
        auto s = TR::ld(src[i]);
        auto t = TR::ld(tgt[i]);
        tgt[i] = TR::st(s > t ? s : t);
      }
      break;
    case 3:
      for (int64_t i = b; i < e; ++i)
        tgt[i] = TR::st(TR::ld(tgt[i]) * TR::ld(src[i]));
      break;
  }
}

// SIMD-cloned entry for the hot gradient dtypes; everything else
// takes the generic template (u8/f16/bf16 go through per-element
// converters the vectorizer handles inside the clone anyway, but only
// f32/f64 carry enough traffic to justify a clone set each).
HVD_SIMD_CLONES void reduce_range_f32(float* t, const float* s, int64_t b,
                                      int64_t e, int op) {
  reduce_into_range<Plain<float>>(t, s, b, e, op);
}

HVD_SIMD_CLONES void reduce_range_f64(double* t, const double* s, int64_t b,
                                      int64_t e, int op) {
  reduce_into_range<Plain<double>>(t, s, b, e, op);
}

HVD_SIMD_CLONES void reduce_range_bf16(uint16_t* t, const uint16_t* s,
                                       int64_t b, int64_t e, int op) {
  reduce_into_range<Bf16>(t, s, b, e, op);
}

template <typename TR>
inline void reduce_range(typename TR::S* t, const typename TR::S* s,
                         int64_t b, int64_t e, int op) {
  reduce_into_range<TR>(t, s, b, e, op);
}

template <>
inline void reduce_range<Plain<float>>(float* t, const float* s, int64_t b,
                                       int64_t e, int op) {
  reduce_range_f32(t, s, b, e, op);
}

template <>
inline void reduce_range<Plain<double>>(double* t, const double* s,
                                        int64_t b, int64_t e, int op) {
  reduce_range_f64(t, s, b, e, op);
}

template <>
inline void reduce_range<Bf16>(uint16_t* t, const uint16_t* s, int64_t b,
                               int64_t e, int op) {
  reduce_range_bf16(t, s, b, e, op);
}

template <typename TR>
void reduce_into_t(void* tgt, const void* src, int64_t len, int op) {
  auto* t = static_cast<typename TR::S*>(tgt);
  auto* s = static_cast<const typename TR::S*>(src);
  parallel_for(len, kGrainElems, [&](int64_t b, int64_t e) {
    reduce_range<TR>(t, s, b, e, op);
  });
}

template <typename TR>
void reduce_kway_t(const void** srcs, int nsrc, int64_t len, void* out,
                   int op) {
  auto* o = static_cast<typename TR::S*>(out);
  parallel_for(len, kGrainElems, [&](int64_t b, int64_t e) {
    std::memcpy(o + b, static_cast<const typename TR::S*>(srcs[0]) + b,
                (e - b) * sizeof(typename TR::S));
    for (int s = 1; s < nsrc; ++s)
      reduce_range<TR>(o, static_cast<const typename TR::S*>(srcs[s]), b, e,
                       op);
  });
}

// Fused arena gather-reduce: nsrc peer deposits at a fixed byte stride
// from base, reduced in one pass per chunk (read k, write 1 — the
// per-peer numpy loop reads AND writes the accumulator every peer).
// skip < 0 means none; init != 0 seeds out from the first non-skipped
// source, else out accumulates in place. Rank order is preserved so
// results stay bitwise identical to the Python loop.
template <typename TR>
void reduce_strided_t(const uint8_t* base, int64_t stride, int nsrc, int skip,
                      int64_t len, void* out, int op, int init) {
  auto* o = static_cast<typename TR::S*>(out);
  parallel_for(len, kGrainElems, [&](int64_t b, int64_t e) {
    int r0 = 0;
    if (init != 0) {
      while (r0 == skip) ++r0;
      std::memcpy(
          o + b,
          reinterpret_cast<const typename TR::S*>(base + r0 * stride) + b,
          (e - b) * sizeof(typename TR::S));
      ++r0;
    }
    for (int r = r0; r < nsrc; ++r) {
      if (r == skip) continue;
      reduce_range<TR>(
          o, reinterpret_cast<const typename TR::S*>(base + r * stride), b, e,
          op);
    }
  });
}

// SIMD-cloned codec inner loops (exports wrap them in parallel_for).
// bf16 both ways and the int8/ef passes are branchless and vectorize;
// fp16 has data-dependent subnormal branches the vectorizer skips,
// but the clones cost nothing there.
HVD_SIMD_CLONES void bf16_encode_range(const float* src, uint16_t* dst,
                                       int64_t b, int64_t e) {
  // float_to_bf16 inlined as straight-line integer ops: gcc refuses
  // to vectorize the call form (the u16 select mid-function defeats
  // its analysis) but takes this shape at every ISA width.
  for (int64_t i = b; i < e; ++i) {
    uint32_t x;
    std::memcpy(&x, src + i, 4);
    uint32_t lsb = (x >> 16) & 1u;
    uint32_t r = (x + 0x7FFFu + lsb) >> 16;
    uint32_t canon = 0x7FC0u | ((x >> 16) & 0x8000u);
    uint32_t nan = (x & 0x7FFFFFFFu) > 0x7F800000u;
    dst[i] = static_cast<uint16_t>(nan ? canon : r);
  }
}

HVD_SIMD_CLONES void bf16_decode_range(const uint16_t* src, float* dst,
                                       int64_t b, int64_t e) {
  for (int64_t i = b; i < e; ++i) dst[i] = bf16_to_float(src[i]);
}

HVD_SIMD_CLONES void fp16_encode_range(const float* src, uint16_t* dst,
                                       int64_t b, int64_t e) {
  for (int64_t i = b; i < e; ++i) dst[i] = float_to_half(src[i]);
}

HVD_SIMD_CLONES void fp16_decode_range(const uint16_t* src, float* dst,
                                       int64_t b, int64_t e) {
  for (int64_t i = b; i < e; ++i) dst[i] = half_to_float(src[i]);
}

HVD_SIMD_CLONES float maxabs_finite_range(const float* src, int64_t b,
                                          int64_t e) {
  float m = 0.0f;
  for (int64_t i = b; i < e; ++i) {
    float a = src[i];
    if (std::isfinite(a)) {
      float t = std::fabs(a);
      if (t > m) m = t;
    }
  }
  return m;
}

HVD_SIMD_CLONES void int8_quant_range(const float* src, int8_t* q,
                                      float scale, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; ++i) {
    float r = nearbyintf(src[i] / scale);  // RNE, like np.round
    int8_t v;
    if (std::isnan(r))
      v = 0;
    else if (r > 127.0f)
      v = 127;
    else if (r < -127.0f)
      v = -127;
    else
      v = static_cast<int8_t>(r);
    q[i] = v;
  }
}

HVD_SIMD_CLONES void int8_dequant_range(const int8_t* q, float* dst,
                                        float scale, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; ++i)
    dst[i] = static_cast<float>(q[i]) * scale;
}

HVD_SIMD_CLONES void ef_update_range(float* residual, const float* pre,
                                     const float* wire, int64_t b,
                                     int64_t e) {
  for (int64_t i = b; i < e; ++i) {
    float r = pre[i] - wire[i];
    residual[i] = std::isfinite(r) ? r : 0.0f;
  }
}

// dtype: 0=f32, 1=f64, 2=i32, 3=i64, 4=u8, 5=f16, 6=bf16.
#define HVD_DISPATCH_DTYPE(dtype, FN, ...)      \
  switch (dtype) {                              \
    case 0:                                     \
      FN<Plain<float>>(__VA_ARGS__);            \
      return 0;                                 \
    case 1:                                     \
      FN<Plain<double>>(__VA_ARGS__);           \
      return 0;                                 \
    case 2:                                     \
      FN<Plain<int32_t>>(__VA_ARGS__);          \
      return 0;                                 \
    case 3:                                     \
      FN<Plain<int64_t>>(__VA_ARGS__);          \
      return 0;                                 \
    case 4:                                     \
      FN<Plain<uint8_t>>(__VA_ARGS__);          \
      return 0;                                 \
    case 5:                                     \
      FN<Half>(__VA_ARGS__);                    \
      return 0;                                 \
    case 6:                                     \
      FN<Bf16>(__VA_ARGS__);                    \
      return 0;                                 \
    default:                                    \
      return -1;                                \
  }

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// k-way elementwise reduction (star data plane). Returns 0 on success,
// -1 on bad dtype/op.
int hvd_reduce(const void** srcs, int nsrc, int64_t len, void* out, int dtype,
               int op) {
  if (nsrc <= 0 || op < 0 || op > 3 || len < 0) return -1;
  HVD_DISPATCH_DTYPE(dtype, reduce_kway_t, srcs, nsrc, len, out, op);
}

// In-place segment reduce: tgt op= src. The ring's recv+reduce step.
int hvd_reduce_into(void* tgt, const void* src, int64_t len, int dtype,
                    int op) {
  if (op < 0 || op > 3 || len < 0) return -1;
  HVD_DISPATCH_DTYPE(dtype, reduce_into_t, tgt, src, len, op);
}

// Fused strided gather-reduce over arena deposit slots (see above).
int hvd_reduce_strided(const void* base, int64_t stride_bytes, int nsrc,
                       int skip, int64_t len, void* out, int dtype, int op,
                       int init) {
  if (nsrc <= 0 || op < 0 || op > 3 || len < 0 || stride_bytes < 0) return -1;
  if (init != 0) {
    int first = (skip == 0) ? 1 : 0;
    if (first >= nsrc) return -1;  // nothing to seed from
  }
  HVD_DISPATCH_DTYPE(dtype, reduce_strided_t,
                     static_cast<const uint8_t*>(base), stride_bytes, nsrc,
                     skip, len, out, op, init);
}

// ---------------------------------------------------------------------------
// Fusion buffer pack/unpack (ref: MemcpyIn/OutFusionBuffer).
int hvd_pack(const void** srcs, const int64_t* nbytes, int n, void* dst) {
  if (n < 0) return -1;
  std::vector<int64_t> offs(n + 1, 0);
  for (int i = 0; i < n; ++i) {
    if (nbytes[i] < 0) return -1;
    offs[i + 1] = offs[i] + nbytes[i];
  }
  char* d = static_cast<char*>(dst);
  Pool* p = (offs[n] >= kParallelThresholdBytes && n > 1) ? pool() : nullptr;
  bool threaded = false;
  if (p != nullptr && p->workers() > 0) {
    std::function<void(int)> job = [&](int i) {
      std::memcpy(d + offs[i], srcs[i], nbytes[i]);
    };
    threaded = p->try_run(n, job);
  }
  if (!threaded)
    for (int i = 0; i < n; ++i) std::memcpy(d + offs[i], srcs[i], nbytes[i]);
  return 0;
}

int hvd_unpack(const void* src, const int64_t* nbytes, int n, void** dsts) {
  if (n < 0) return -1;
  int64_t off = 0;
  for (int i = 0; i < n; ++i) {
    if (nbytes[i] < 0) return -1;
    std::memcpy(dsts[i], static_cast<const char*>(src) + off, nbytes[i]);
    off += nbytes[i];
  }
  return 0;
}

// ---------------------------------------------------------------------------
// Wire codec passes (common/compression.py fallbacks define the wire
// contract; these are bit-identical, GIL-free, pooled).

int hvd_bf16_encode(const float* src, int64_t n, uint16_t* dst) {
  if (n < 0) return -1;
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    bf16_encode_range(src, dst, b, e);
  });
  return 0;
}

int hvd_bf16_decode(const uint16_t* src, int64_t n, float* dst) {
  if (n < 0) return -1;
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    bf16_decode_range(src, dst, b, e);
  });
  return 0;
}

int hvd_fp16_encode(const float* src, int64_t n, uint16_t* dst) {
  if (n < 0) return -1;
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    fp16_encode_range(src, dst, b, e);
  });
  return 0;
}

int hvd_fp16_decode(const uint16_t* src, int64_t n, float* dst) {
  if (n < 0) return -1;
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    fp16_decode_range(src, dst, b, e);
  });
  return 0;
}

// int8 with a little-endian f32 scale header at dst[0:4], then n
// quantized bytes: scale = max|finite|/127 (f64 divide, stored f32 —
// the exact arithmetic of Int8Codec.encode), q = clip(rne(a/scale)),
// nan -> 0, +/-inf -> +/-127.
int hvd_int8_encode(const float* src, int64_t n, uint8_t* dst) {
  if (n < 0) return -1;
  std::atomic<uint32_t> maxbits{0};  // non-negative floats order as ints
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    uint32_t mb = float_to_bits(maxabs_finite_range(src, b, e));
    uint32_t cur = maxbits.load(std::memory_order_relaxed);
    while (mb > cur &&
           !maxbits.compare_exchange_weak(cur, mb, std::memory_order_relaxed))
      ;
  });
  float maxabs = bits_to_float(maxbits.load(std::memory_order_relaxed));
  double scale_d =
      static_cast<double>(maxabs) / 127.0;
  float scale = (std::isfinite(scale_d) && scale_d > 0.0)
                    ? static_cast<float>(scale_d)
                    : 0.0f;
  std::memcpy(dst, &scale, 4);  // LE on every supported host
  int8_t* q = reinterpret_cast<int8_t*>(dst + 4);
  if (!(std::isfinite(scale_d) && scale_d > 0.0)) {
    std::memset(q, 0, static_cast<size_t>(n));
    return 0;
  }
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    int8_quant_range(src, q, scale, b, e);
  });
  return 0;
}

int hvd_int8_decode(const uint8_t* src, int64_t n, float* dst) {
  if (n < 0) return -1;
  float scale;
  std::memcpy(&scale, src, 4);
  const int8_t* q = reinterpret_cast<const int8_t*>(src + 4);
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    int8_dequant_range(q, dst, scale, b, e);
  });
  return 0;
}

// Error-feedback residual: residual = pre - wire, non-finite lanes
// reset to 0 (ErrorFeedback.update's saturation defense).
int hvd_ef_update(float* residual, const float* pre, const float* wire,
                  int64_t n) {
  if (n < 0) return -1;
  parallel_for(n, kGrainElems, [&](int64_t b, int64_t e) {
    ef_update_range(residual, pre, wire, b, e);
  });
  return 0;
}

// ---------------------------------------------------------------------------
// Adasum (ref: adasum.h:100-280). vecs: nvec pointers to f64 arrays of
// length n, combined IN PLACE so that every slot holds the Adasum result.
// nvec must be a power of two. Dot/norm accumulation is f64 end-to-end
// like the reference's DispatchComputeDotAndNormSqrds.
int hvd_adasum(double** vecs, int nvec, int64_t n) {
  if (nvec <= 0 || (nvec & (nvec - 1)) != 0) return -1;
  std::vector<std::vector<double>> scratch(nvec);
  for (int stride = 1; stride < nvec; stride <<= 1) {
    // Each unordered pair (i, i^stride) combines symmetrically.
    for (int i = 0; i < nvec; ++i) {
      int j = i ^ stride;
      if (j < i) continue;
      const double* a = vecs[i];
      const double* b = vecs[j];
      double dot = 0.0, na = 0.0, nb = 0.0;
      for (int64_t k = 0; k < n; ++k) {
        dot += a[k] * b[k];
        na += a[k] * a[k];
        nb += b[k] * b[k];
      }
      double ca = na > 0 ? 1.0 - dot / (2.0 * na) : 1.0;
      double cb = nb > 0 ? 1.0 - dot / (2.0 * nb) : 1.0;
      auto& tmp = scratch[i];
      tmp.resize(n);
      parallel_for(n, kGrainElems, [&](int64_t b0, int64_t e0) {
        for (int64_t k = b0; k < e0; ++k) tmp[k] = ca * a[k] + cb * b[k];
      });
      std::memcpy(vecs[i], tmp.data(), n * sizeof(double));
      std::memcpy(vecs[j], tmp.data(), n * sizeof(double));
    }
  }
  return 0;
}

// ---------------------------------------------------------------------------
// Bit-vector ops (ref: response_cache.h). op: 0=and, 1=or.
void hvd_words_op(uint64_t* acc, const uint64_t* other, int n, int op) {
  if (op == 0)
    for (int i = 0; i < n; ++i) acc[i] &= other[i];
  else
    for (int i = 0; i < n; ++i) acc[i] |= other[i];
}

// Worker threads the pool runs with (callers add themselves on top).
int hvd_threads() { return (pool() != nullptr ? pool()->workers() : 0) + 1; }

int hvd_abi_version() { return 2; }

}  // extern "C"
