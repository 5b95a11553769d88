"""Per-op device-time profile of a train step via jax.profiler.trace.

Produces the bucket tables in docs/benchmarks.md: traces one scan
chunk of the requested model's train step on the real chip, then
aggregates the device lane of the Chrome trace by op family and prints
ms/step per bucket. Only the process that holds the chip can trace it.

Usage:
    python scripts/profile_step.py                 # gpt2-small flash
    python scripts/profile_step.py --model resnet50 --batch 256
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.utils import chrome_trace  # noqa: E402


def capture(model: str, batch: int, seq: int, chunk: int, outdir: str):
    import jax

    from bench import _build, _make_scan_step

    kw = {}
    if model.startswith("gpt2"):
        kw = {"model_kw": {"attn_impl": "flash", "max_len": seq},
              "seq_len": seq}
    state, step_fn, inputs, labels, _, mesh = _build(
        model, 1, batch, **kw)
    scan_fn = _make_scan_step(step_fn, mesh, chunk)
    state, losses = scan_fn(state, inputs, labels)   # compile + warm
    jax.device_get(losses)
    with jax.profiler.trace(outdir):
        state, losses = scan_fn(state, inputs, labels)
        jax.device_get(losses)


def aggregate(outdir: str, steps: int):
    # Shared glob/gzip/parse helper (utils/chrome_trace) — one reader
    # for this script, engine/mesh_timeline.py and the tracing plane.
    events = chrome_trace.load_profiler_events(outdir)
    if events is None:
        raise RuntimeError(
            f"no Chrome trace captured under {outdir} — the profiler "
            "produced nothing"
        )
    device_pids = {
        e["pid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "TPU" in e.get("args", {}).get("name", "")
    }
    buckets = collections.Counter()
    counts = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        n = e["name"]
        if n.startswith(("while", "jit_")) or not n.strip() \
                or n.isdigit():
            continue  # container frames double-count their children
        fam = ("attention_kernels" if re.match(r"attn[.\d]*$", n)
               else re.sub(r"[.\d]+$", "", n))
        buckets[fam] += e["dur"]
        counts[fam] += 1
    total = sum(buckets.values())
    rows = [
        {"bucket": k, "ms_per_step": round(v / steps / 1e3, 3),
         "ops_per_step": counts[k] // steps,
         "share_pct": round(100 * v / total, 1)}
        for k, v in buckets.most_common()
        if v / steps / 1e3 >= 0.01
    ]
    return {"total_ms_per_step": round(total / steps / 1e3, 2),
            "buckets": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-small")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    outdir = tempfile.mkdtemp(prefix="hvdtpu_profile_")
    try:
        capture(args.model, args.batch, args.seq, args.chunk, outdir)
        result = aggregate(outdir, args.chunk)
        print(json.dumps(result, indent=1))
    finally:
        if args.keep_trace:
            print(f"trace kept at {outdir}", file=sys.stderr)
        else:
            shutil.rmtree(outdir, ignore_errors=True)


if __name__ == "__main__":
    main()
