"""Share of the window the host spent enqueueing jitted chunk calls,
copies of their numpy arguments included (self time of the span
``sim.dispatch``)."""

from perfbench import spans


def read(run: dict) -> float | None:
    return spans.share(run, ("sim.dispatch",))
