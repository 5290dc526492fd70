"""Share of the window the host spent preparing the scan's inputs and
building its results (self time of the spans ``sim.analyse``,
``sim.stage`` and ``sim.report``)."""

from perfbench import spans


def read(run: dict) -> float | None:
    return spans.share(run, ("sim.analyse", "sim.stage", "sim.report"))
