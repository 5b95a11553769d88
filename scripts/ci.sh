#!/usr/bin/env bash
# Minimal CI pipeline (ref: .buildkite/gen-pipeline.sh:10-27 runs the
# test suite across framework combos; this single-node variant runs the
# full suite, the multichip sharding dryrun, and a CPU bench smoke).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "=== native core build (cc/libhvdtpu.so — docs/native.md) ==="
# Build up front so every stage below exercises the C++ kernels; a
# build failure is a CI failure, not a silent numpy fallback.
make -C horovod_tpu/cc -s
python - <<'EOF'
from horovod_tpu.cc import native
st = native.status()
assert st["loaded"], f"native core built but failed to load: {st}"
print(f"native core loaded: abi {st['abi']}, {st['threads']} threads, "
      f"{sum(st['kernels'].values())}/{len(st['kernels'])} kernels")
EOF

echo "=== engine/transport subset, native kernels ON ==="
ENGINE_SUBSET="tests/test_native.py tests/test_engine.py tests/test_ring.py \
  tests/test_transport.py tests/test_hierarchical.py tests/test_compression.py"
python -m pytest $ENGINE_SUBSET -q -m 'not slow'

echo "=== engine/transport subset, HOROVOD_DISABLE_NATIVE=1 (numpy fallback parity) ==="
HOROVOD_DISABLE_NATIVE=1 python -m pytest $ENGINE_SUBSET -q -m 'not slow'

echo "=== unit + integration tests (fast tier — FULLY GREEN tier-1) ==="
# ANY failed test fails CI: there is no known-failure allowance.
if ! python -m pytest tests/ -q -m 'not slow'; then
  echo "tier-1 is no longer fully green"
  exit 1
fi

echo "=== slow tier (full adapter / chaos coverage) ==="
python -m pytest tests/ -x -q -m slow

echo "=== telemetry smoke (metrics endpoint + snapshot + health plane: /timeseries, /alerts, straggler fire/resolve) ==="
python scripts/telemetry_smoke.py

echo "=== tracing smoke (merged /trace + post-mortem on injected sever) ==="
python scripts/trace_smoke.py

echo "=== data-plane perf smoke (tcp + shm + hierarchical, exact byte accounting per transport) ==="
python scripts/perf_smoke.py

echo "=== ZeRO perf smoke (np=4 sharded optimizer: exact gradient-allreduce + segment-allgather byte accounting, bitwise parity vs replicated) ==="
python scripts/perf_smoke.py zero

echo "=== chaos smoke over shared memory (wedge detection while data rides shm) ==="
python scripts/chaos_smoke.py --transport shm --wedge

echo "=== elastic recovery smoke (wedge 1 of 4, survivors resume at np=3) ==="
python scripts/elastic_smoke.py

echo "=== preemption smoke (announced drain: zero lost steps, preemption-bucket attribution, graceful beats timeout goodput) ==="
python scripts/preemption_smoke.py

echo "=== durability smoke (kill ALL ranks, restart, bitwise resume) ==="
python scripts/checkpoint_smoke.py

echo "=== checkpoint overhead smoke (background write <5% of step time) ==="
python scripts/checkpoint_smoke.py --overhead

echo "=== serving smoke (4-rank continuous batching: p50/p99 under concurrent load, weight hot-swap mid-traffic, wedged-replica eviction) ==="
python scripts/serving_smoke.py

echo "=== perf report (host loopback stages; warn vs committed scripts/perf_baseline.json; docs/health.md) ==="
python scripts/perf_report.py --quick --out /tmp/hvd_perf1.json

echo "=== perf gate self-test (clean back-to-back must pass; injected 2x slowdown must trip) ==="
python scripts/perf_report.py --quick --out /tmp/hvd_perf2.json \
    --baseline /tmp/hvd_perf1.json --gate
if python scripts/perf_report.py --replay /tmp/hvd_perf2.json \
    --baseline /tmp/hvd_perf1.json --inject-slowdown 2.0 --gate; then
  echo "perf gate FAILED TO TRIP on an injected 2x slowdown"
  exit 1
fi

echo "=== multichip sharding dryrun (8 virtual devices) ==="
python __graft_entry__.py

echo "CI OK"
