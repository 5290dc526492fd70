"""Share of the window the host spent blocked on the device, reading the
scan's results back (self time of the span ``sim.wait``)."""

from perfbench import spans


def read(run: dict) -> float | None:
    return spans.share(run, ("sim.wait",))
