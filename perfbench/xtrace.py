"""Reduce a JAX profiler trace of the measured window to device numbers.

What is read, all on the trace's own clock:

* the window: the host span ``bench.window`` the harness opens around it;
* program executions: events of the ``XLA Modules`` line of each device
  plane (``/device:TPU:<n>``), one per launch; busy time is the union of
  their intervals inside the window, so overlapping events count once
  (the ``XLA Ops`` line stands in where a plane has no modules line);
* device operations: events of the ``XLA Ops`` line, read only for the
  breakdown. A program whose loop body the profiler records op by op
  writes millions of them, so the line is read up to ``MAX_OPS`` events;
  past that the breakdown lists programs instead of operations.
* the harness's host spans (``question.build``, ``question.sweep``), which
  name each idle gap by what the host was doing in it.

``reduce_events`` takes plain event lists so a recorded trace can be
checked without the profiler (see ``perfbench/fixtures``).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("question.build", "question.sweep")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MAX_OPS = 2_000_000
TOP = 10


def _events(line, cap=None):
    out = []
    for e in line.events:
        if cap is not None and len(out) >= cap:
            return out, True
        out.append([e.name, e.start_ns, e.start_ns + e.duration_ns])
    return out, False


def load(trace_dir: str) -> dict:
    """Event lists (``[name, start_ns, end_ns]``) from the newest trace."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"window": None, "host": [], "devices": {}}
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: dict = {"window": None, "host": [], "devices": {}}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": [], "ops_truncated": False}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev["modules"] = _events(line)[0]
                elif line.name == OPS_LINE:
                    dev["ops"], dev["ops_truncated"] = _events(line, MAX_OPS)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    span = [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    if e.name == WINDOW_SPAN:
                        out["window"] = span[1:]
                    elif e.name in HOST_SPANS:
                        out["host"].append(span)
    return out


def union(intervals) -> list[list[float]]:
    """Merged ``[start, end]`` intervals, sorted."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events, w0, w1):
    return [(max(s, w0), min(e, w1), n) for n, s, e in events
            if e > w0 and s < w1]


def reduce_events(ev: dict) -> dict | None:
    """Busy and window seconds, program launches, the device operations
    that took most time and the longest idle gaps, averaged over devices
    where a per-device number. None when the trace has no window or no
    device event inside it."""
    if ev.get("window") is None:
        return None
    w0, w1 = ev["window"]
    devices = [d for d in ev["devices"].values()
               if _clip(d["modules"] or d["ops"], w0, w1)]
    if not devices:
        return None
    busy_ns = 0.0
    launches = 0
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for dev in devices:
        spans = _clip(dev["modules"] or dev["ops"], w0, w1)
        merged = union([(s, e) for s, e, _ in spans])
        busy_ns += sum(e - s for s, e in merged)
        whole_ops = dev["ops"] and not dev["ops_truncated"]
        for s, e, name in (_clip(dev["ops"], w0, w1) if whole_ops
                           else spans):
            op_time[name] += e - s
        launches += len(_clip(dev["modules"], w0, w1))
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if w1 > edge:
            gaps.append((edge, w1))
    n = len(devices)
    host = [(s, e, name) for name, s, e in ev["host"]]

    def host_doing(g0, g1):
        best, name = 0.0, "between questions"
        for s, e, span in host:
            cover = min(e, g1) - max(s, g0)
            if cover > best:
                best, name = cover, span
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "launches": launches / n,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[host_doing(g0, g1), (g1 - g0) / 1e9]
                      for g0, g1 in gaps[:TOP]],
    }
