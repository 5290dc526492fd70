"""Spans and counters of the simulator's own host and device time.

Spans are ``jax.profiler.TraceAnnotation``s: with no profiler running one
costs a ``TraceMe`` check; under ``jax.profiler.trace`` it lands on the
host plane, on the clock of the device planes. Counters are totals since
the process started, in :data:`COUNTS`. The increments a span is opened
with, or is given by :func:`count` while it is open, also ride on that
span as its arguments, so a trace carries the counts of exactly the
window it recorded.

This is the simulator's speed; :mod:`repro.obs` is telemetry of the
*simulated* hardware.
"""

from __future__ import annotations

from collections import Counter

#: ``sim.<name>`` -> total since the process started
COUNTS: Counter[str] = Counter()


def span(name: str, **counts: int):
    """A profiler span ``name``; ``counts`` are added to :data:`COUNTS`
    as ``sim.<key>`` and attached to the span under the same keys."""
    from jax.profiler import TraceAnnotation

    args = {f"sim.{k}": int(v) for k, v in counts.items()}
    COUNTS.update(args)
    return TraceAnnotation(name, **args)


def count(open_span, **counts: int) -> None:
    """Add ``counts`` to :data:`COUNTS` as ``sim.<key>`` and attach them to
    ``open_span`` (a :func:`span` inside its ``with``), for counts known
    only once the span's work is done."""
    args = {f"sim.{k}": int(v) for k, v in counts.items()}
    COUNTS.update(args)
    open_span.set_metadata(**args)
