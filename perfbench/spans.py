#!/usr/bin/env python3
"""The simulator's own spans and counters, read from the window's trace.

The program (``repro.core.probe``) opens ``sim.*`` profiler spans at the
layer boundaries of ``sweep_workload(..., backend="fast")`` and attaches
its counter increments (``sim.chunk_calls``, ``sim.scan_steps``,
``sim.useful_steps``, ``sim.h2d_bytes``) to them as span arguments. A
traced run of the harness records them on the host plane of its trace,
under ``TRACE_DIR``, on the clock of the device planes. From there:

* ``self_s``: each span's self time inside ``bench.window``, that is its
  time there minus what the spans nested in it cover, for the harness's
  question spans and every ``sim.*`` span;
* ``counters``: each counter summed over the spans that start inside
  the window;
* ``gap_owner``: names an interval, such as a device idle gap, by the
  span that is innermost for most of it.

A window with no ``sim.*`` span, as a program without the probe records,
reads as nothing, and so does every reader of these numbers.

The readers take the numbers from ``run["spans"]`` and
``run["counters"]``; where the harness passes neither, they are filled
from the trace here. Run as a script to print them, with the device's
idle gaps named by ``gap_owner``:

    python3 perfbench/spans.py [trace_dir]
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
#: where ``run.py`` writes the trace of a traced run's window
TRACE_DIR = ROOT / ".bench_out" / "trace"
WINDOW_SPAN = "bench.window"
QUESTION_SPANS = ("question.build", "question.sweep")
PREFIX = "sim."
IDLE_LABEL = "between questions"
TOP = 10


def _kept(name: str) -> bool:
    return name in QUESTION_SPANS or name.startswith(PREFIX)


def load(trace_dir: str | os.PathLike) -> dict:
    """The window and the kept host spans (``[name, start_ns, end_ns,
    line, {argument: value}]``) of the newest trace under ``trace_dir``."""
    out: dict = {"window": None, "spans": []}
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return out
    from jax.profiler import ProfileData

    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    out["window"] = [e.start_ns, e.start_ns + e.duration_ns]
                elif _kept(e.name):
                    out["spans"].append(
                        [e.name, e.start_ns, e.start_ns + e.duration_ns, li,
                         {k: v for k, v in e.stats if k.startswith(PREFIX)}])
    return out


def self_times(events, w0: float, w1: float) -> dict[str, float]:
    """Nanoseconds of self time per span name inside ``[w0, w1]``.

    Spans of one line (one host thread) nest by time, so a span's children
    are disjoint and its self time is its clipped length minus theirs."""
    out: dict[str, float] = Counter()
    by_line: dict[int, list] = {}
    for name, s, e, line, _ in events:
        by_line.setdefault(line, []).append((s, -e, name))
    for line in by_line.values():
        stack: list[tuple[float, str]] = []     # (end, name) of open spans
        for s, neg_e, name in sorted(line):
            e = -neg_e
            while stack and stack[-1][0] <= s:
                stack.pop()
            inside = max(0.0, min(e, w1) - max(s, w0))
            out[name] += inside
            if stack:
                out[stack[-1][1]] -= inside
            stack.append((e, name))
    return dict(out)


def gap_owner(events, g0: float, g1: float) -> str:
    """The span that is innermost for most of ``[g0, g1]``: the largest
    self time inside it, so a gap over many waits is named ``sim.wait``."""
    inside = self_times(events, g0, g1)
    name = max(inside, key=inside.get, default=None)
    return name if name is not None and inside[name] > 0 else IDLE_LABEL


def summary(ev: dict) -> dict | None:
    """``window_s``, ``self_s`` per span name and ``counters`` of the
    window; None when no ``sim.*`` span starts inside it."""
    if ev.get("window") is None:
        return None
    w0, w1 = ev["window"]
    inside = [sp for sp in ev["spans"] if w0 <= sp[1] <= w1]
    if not any(sp[0].startswith(PREFIX) for sp in inside):
        return None
    total: Counter = Counter()
    for sp in inside:
        total.update(sp[4])
    return {"window_s": (w1 - w0) / 1e9,
            "self_s": {k: v / 1e9 for k, v in
                       self_times(inside, w0, w1).items()},
            "counters": dict(total)}


def _fill(run: dict) -> None:
    if "spans" in run or "counters" in run:
        return
    got = summary(load(TRACE_DIR))
    run["spans"] = got and {k: got[k] for k in ("window_s", "self_s")}
    run["counters"] = got and got["counters"]


def counters(run: dict) -> dict | None:
    """The window's counters, or None where there are none to read."""
    _fill(run)
    return run.get("counters")


def spans(run: dict) -> dict | None:
    """``window_s`` and ``self_s`` of the window, or None."""
    _fill(run)
    return run.get("spans")


def share(run: dict, names) -> float | None:
    """Self time of the spans ``names`` as a share of the window."""
    sp = spans(run)
    if not sp or sp["window_s"] <= 0:
        return None
    return sum(sp["self_s"].get(n, 0.0) for n in names) / sp["window_s"]


def main(argv: list[str]) -> int:
    sys.path[:0] = [str(ROOT)]
    from perfbench import xtrace

    trace_dir = argv[0] if argv else str(TRACE_DIR)
    ev = load(trace_dir)
    got = summary(ev)
    if got is None:
        print(json.dumps({"spans": None}))
        return 1
    w0, w1 = ev["window"]
    busy = xtrace.union(
        (max(s, w0), min(e, w1))
        for dev in xtrace.load(trace_dir)["devices"].values()
        for _, s, e in (dev["modules"] or dev["ops"]) if e > w0 and s < w1)
    gaps, edge = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    inside = [sp for sp in ev["spans"] if w0 <= sp[1] <= w1]
    got["share"] = {k: v / got["window_s"] for k, v in
                    sorted(got["self_s"].items(), key=lambda kv: -kv[1])}
    got["idle_gaps"] = [[gap_owner(inside, g0, g1), (g1 - g0) / 1e9]
                        for g0, g1 in gaps[:TOP]]
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
