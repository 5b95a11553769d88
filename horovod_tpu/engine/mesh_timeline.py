"""Device-side timeline for the traced/GSPMD path.

The eager engine's `Timeline` covers host-side negotiation and backend
activities; under `jit` the collectives are compiled into the XLA
module, so their timings only exist device-side. The reference has the
same split — its GPU ops record CUDA events into the timeline after the
fact (ref: horovod/common/ops/gpu_operations.h:110-118). Here the
device record comes from the XLA profiler: `MeshTimeline.capture()`
wraps any traced-step region, then splices the profiler's device lanes
into one Chrome-trace file, with the collective ops (all-reduce /
all-gather / all-to-all / collective-permute / reduce-scatter) pulled
onto a dedicated "ICI collectives" lane so step compute and
communication read side-by-side in chrome://tracing or Perfetto.

Usage::

    tl = MeshTimeline("mesh_timeline.json")   # or HOROVOD_TIMELINE env
    with tl.capture():
        for _ in range(3):
            state, loss = step(state, batch)
        jax.block_until_ready(loss)
    # mesh_timeline.json now holds device lanes + collective lane.
"""
from __future__ import annotations

import os
import re
import shutil
import tempfile
from contextlib import contextmanager
from typing import List, Optional

from ..utils import chrome_trace, clock
from ..utils import env as env_cfg

# XLA op-name fragments that identify cross-device communication.
_COLLECTIVE_PAT = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|psum|ppermute|collective-broadcast",
    re.IGNORECASE,
)
_COLLECTIVE_LANE_PID = 999


class MeshTimeline:
    def __init__(self, output_path: Optional[str] = None,
                 use_env: bool = True):
        if output_path is None and use_env:
            base = env_cfg.get_str(env_cfg.TIMELINE) or None
            if base:
                root, ext = os.path.splitext(base)
                output_path = f"{root}.mesh{ext or '.json'}"
        self.output_path = output_path
        self.enabled = bool(output_path)

    @contextmanager
    def capture(self):
        """Profile the enclosed traced-step region and write the spliced
        Chrome trace on exit. No-op (still yields) when disabled."""
        if not self.enabled:
            yield
            return
        import jax

        tmp = tempfile.mkdtemp(prefix="hvd_mesh_tl_")
        jax.profiler.start_trace(tmp)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            try:
                self._splice(tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)

    # ------------------------------------------------------------------
    def _splice(self, profile_dir: str):
        # Shared glob/gzip/parse helper (utils/chrome_trace) — the same
        # reader the tracing plane's analyzers use.
        events = chrome_trace.load_profiler_events(profile_dir)
        if events is None:
            return
        out: List[dict] = []
        device_pids = set()
        for ev in events:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                pname = (ev.get("args") or {}).get("name", "")
                if "host" not in pname.lower():
                    device_pids.add(ev["pid"])
                out.append(ev)
        for ev in events:
            if ev.get("ph") == "M":
                continue
            if ev.get("pid") in device_pids:
                out.append(ev)
                # Duplicate communication ops onto the dedicated lane.
                if ev.get("ph") == "X" and _COLLECTIVE_PAT.search(
                        ev.get("name", "")):
                    c = dict(ev)
                    c["pid"] = _COLLECTIVE_LANE_PID
                    c["tid"] = 0
                    out.append(c)
        out.append({"ph": "M", "name": "process_name",
                    "pid": _COLLECTIVE_LANE_PID,
                    "args": {"name": "ICI collectives"}})
        # The wall-clock identity of this process's host-trace origin
        # rides along so the host timeline (engine/timeline.py, same
        # anchor) can be laid next to these device lanes offline.
        chrome_trace.write_trace(
            self.output_path, out,
            metadata={"horovod_clock": clock.anchor_meta()})
