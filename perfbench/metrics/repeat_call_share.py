"""Share of the jitted scan chunk calls that rescan a trace the same
sweep has already scanned under the same load signature: counter
``sim.repeat_chunk_calls`` over ``sim.chunk_calls``, both carried by the
``sim.dispatch`` spans of the window's trace. Each such span carries
either ``sim.repeat_chunk_calls`` or ``sim.fresh_chunk_calls``; a program
that carries neither reads as nothing."""

from perfbench import spans

MARKS = ("sim.repeat_chunk_calls", "sim.fresh_chunk_calls")


def read(run: dict) -> float | None:
    c = spans.counters(run)
    if (c is None or not c.get("sim.chunk_calls")
            or not any(k in c for k in MARKS)):
        return None
    return c.get("sim.repeat_chunk_calls", 0) / c["sim.chunk_calls"]
