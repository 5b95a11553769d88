#!/usr/bin/env python3
"""BENCHMARK.json's command: one process, one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Refuses any platform but the TPU and any device kind whose
peaks it does not know: non-zero exit, no result line, no CPU fallback.
Informational lines (`info: {...}`) come first; the LAST line of stdout
is the result object the contract fixes. README.md in this directory
describes a run.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the traced run's profiler files here "
                         "(default: a temporary directory, removed)")
    args = ap.parse_args()

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # Cache the small programs too (the reference's pieces, the
    # checksum): every run is a new process and would compile them anew.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from benchmark import harness, peaks

    devices = jax.devices()
    try:
        chip_peaks = peaks.for_device(devices[0])
    except peaks.UnknownDevice as exc:
        print(f"benchmark/run.py: {exc}", file=sys.stderr)
        return 2
    print("info: " + json.dumps({"compile_cache": cache_dir}), flush=True)
    result = harness.run_cell(
        ROOT / "BENCHMARK.json", args.workload, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        peaks=chip_peaks, t0=T0, trace_dir=args.trace_dir)
    print(json.dumps(result), flush=True)
    # The numbers `correct` rests on, each beside its limit, as the last
    # lines of stderr: what a record of a failed run keeps.
    for name, row in result["compared"].items():
        print(f"compared: {name} {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
