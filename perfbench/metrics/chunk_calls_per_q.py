"""Jitted scan chunk calls per question answered (counter
``sim.chunk_calls``, carried by the ``sim.dispatch`` spans of the window's
trace)."""

from perfbench import spans


def read(run: dict) -> float | None:
    c = spans.counters(run)
    if c is None or not run.get("questions"):
        return None
    return c.get("sim.chunk_calls", 0) / run["questions"]
