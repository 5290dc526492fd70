"""Megabytes copied from the host to the device for the scan per question
answered (counter ``sim.h2d_bytes``: the numpy arguments of every chunk
call and the initial carry)."""

from perfbench import spans


def read(run: dict) -> float | None:
    c = spans.counters(run)
    if c is None or not run.get("questions"):
        return None
    return c.get("sim.h2d_bytes", 0) / 1e6 / run["questions"]
