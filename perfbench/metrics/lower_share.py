"""Share of the window the host spent lowering GEMMs to compiled traces
(self time of the span ``sim.lower``)."""

from perfbench import spans


def read(run: dict) -> float | None:
    return spans.share(run, ("sim.lower",))
