"""Chrome-trace file plumbing shared by every trace consumer.

The glob/gzip/parse dance over a ``jax.profiler`` output directory is
needed by ``engine/mesh_timeline.py`` (device-lane splicing) and by
the tracing plane's analyzers (scripts/critical_path.py) for merged
traces and post-mortems. One module for both.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from typing import List, Optional


def read_trace_file(path: str):
    """Load one Chrome-trace JSON file (.json or .json.gz). Returns the
    parsed document: either a top-level event list or an object with a
    ``traceEvents`` key — see ``trace_events`` for the normalizer."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def trace_events(doc) -> List[dict]:
    """Normalize a Chrome-trace document to its event list. The format
    allows both a bare top-level array and {"traceEvents": [...]} — and
    ``data.get`` on a list raises before any default applies, which is
    exactly the bug this helper exists to fix once."""
    if isinstance(doc, list):
        return doc
    return doc.get("traceEvents", [])


def load_profiler_events(profile_dir: str) -> Optional[List[dict]]:
    """Events of the newest trace.json(.gz) under a profiler output dir
    (``jax.profiler`` nests them under plugins/profile/<ts>/). None when
    the profiler produced nothing."""
    paths = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(profile_dir, "**", "*.trace.json"),
                    recursive=True)
    )
    if not paths:
        return None
    return trace_events(read_trace_file(paths[-1]))


def instant(name: str, ts_us: float, pid: int = 0, tid: int = 0,
            scope: str = "p", cat: Optional[str] = None,
            args: Optional[dict] = None) -> dict:
    """One Chrome-trace instant event ("ph": "i") — the vertical marker
    lane-annotation form the lifecycle events plane (docs/events.md)
    uses to land re-mesh/drain/swap markers inline with spans. `scope`:
    "g" draws the line across the whole trace, "p" across the process
    lane, "t" on one thread."""
    ev = {"ph": "i", "name": name, "pid": pid, "tid": tid,
          "ts": ts_us, "s": scope}
    if cat:
        ev["cat"] = cat
    if args:
        ev["args"] = args
    return ev


def instant_events(doc) -> List[dict]:
    """Every instant event in a trace document (tests + analyzers)."""
    return [e for e in trace_events(doc) if e.get("ph") == "i"]


def write_trace(path: str, events: List[dict], metadata: Optional[dict] = None):
    """Write events as a ``{"traceEvents": [...]}`` document (the object
    form — Perfetto accepts extra top-level keys, so tool metadata rides
    along without confusing the viewer). Atomic (tmp+rename) so a crash
    mid-dump never leaves a torn JSON where a viewer expects a trace."""
    doc = {"traceEvents": events}
    if metadata:
        doc.update(metadata)
    from . import atomic_file

    atomic_file.atomic_write(path, lambda f: json.dump(doc, f), mode="w")
