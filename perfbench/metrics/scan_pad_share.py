"""Share of the scanned (step, lane) pairs that are padding: 1 minus the
counter ``sim.useful_steps`` (real instructions times real design lanes)
over ``sim.scan_steps`` (chunk length times lanes, per chunk call)."""

from perfbench import spans


def read(run: dict) -> float | None:
    c = spans.counters(run)
    if c is None or not c.get("sim.scan_steps"):
        return None
    return 1.0 - c.get("sim.useful_steps", 0) / c["sim.scan_steps"]
