"""The simulator's own counters and spans (``repro.core.probe``).

The counters of a jax sweep equal the host arithmetic of its chunk calls,
and a sweep under the profiler returns the same reports as one without.
"""

import dataclasses

import jax
import pytest

from repro.core import GemmSpec, fastsim, get_design, probe, sweep_workload
from repro.core.fastsim import (StreamModelParams, run_cores, sweep_trace,
                                sweep_traces)
from repro.core.isa import NUM_TREGS
from repro.core.tiling import ALG1_POLICY
from repro.core.trace import gemm_trace

CHUNK = 16
SPECS = [GemmSpec("g0", 64, 128, 96), GemmSpec("g1", 192, 64, 160),
         GemmSpec("g2", 32, 256, 64)]
#: bytes per scanned step of the 15 MM columns: a valid flag, three int32
#: registers, three "written in the scan" flags, three float64 constants,
#: the reuse flag, tm, issue time, "has a store" flag and its issue time
COLUMN_BYTES = 1 + 3 * 4 + 3 * 1 + 3 * 8 + 1 + 8 + 8 + 1 + 8
#: per lane: wl fs dr (float64) and wlbp wls pipe (bool)
DESIGN_BYTES = 3 * 8 + 3 * 1
#: per lane: the register file and the eight other slots of the MM carry
CARRY_BYTES = 8 * NUM_TREGS + 8 + 3 * 8 + 1 + 2 * 8 + 4


def _designs():
    # two load-signature groups: three Table designs (padded to four
    # lanes) and two with a slower load (two lanes)
    table = [get_design(n) for n in ("BASE", "RASA-PIPE", "RASA-WLBP")]
    slow = [dataclasses.replace(get_design(n), name=f"{n}-lat9",
                                load_latency=9)
            for n in ("RASA-DB-WLS", "RASA-DM-WLBP")]
    return table + slow


def _sweep():
    return sweep_workload(SPECS, _designs(), backend="jax")


@pytest.fixture
def short_chunks(monkeypatch):
    monkeypatch.setattr(fastsim, "CHUNK", CHUNK)


def test_counters_equal_the_host_arithmetic(short_chunks):
    want = {"sim.chunk_calls": 0, "sim.scan_steps": 0,
            "sim.useful_steps": 0, "sim.h2d_bytes": 0}
    for spec in SPECS:
        n_mm = gemm_trace(spec, ALG1_POLICY).n_mm
        calls = -(-n_mm // CHUNK)
        for members, lanes in ((3, 4), (2, 2)):
            want["sim.chunk_calls"] += calls
            want["sim.scan_steps"] += calls * CHUNK * lanes
            want["sim.useful_steps"] += n_mm * members
            want["sim.h2d_bytes"] += (
                lanes * CARRY_BYTES
                + calls * (CHUNK * COLUMN_BYTES + lanes * DESIGN_BYTES))
    assert max(-(-gemm_trace(s, ALG1_POLICY).n_mm // CHUNK)
               for s in SPECS) > 1
    before = probe.COUNTS.copy()
    _sweep()
    got = probe.COUNTS.copy()
    got.subtract(before)
    assert {k: got[k] for k in want} == want


def test_profiler_on_and_off_give_identical_reports(short_chunks, tmp_path):
    off = _sweep()
    with jax.profiler.trace(str(tmp_path)):
        on = _sweep()
    assert on == off
    assert list(tmp_path.rglob("*.xplane.pb"))


def test_span_is_a_trace_annotation_that_counts():
    before = probe.COUNTS["sim.chunk_calls"]
    with probe.span("sim.dispatch", chunk_calls=3) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    with probe.span("sim.wait"):
        pass
    assert probe.COUNTS["sim.chunk_calls"] == before + 3


@pytest.mark.parametrize("layout", ["sweep_trace", "cores", "packed"])
def test_generic_layouts_count_their_chunk_calls(layout):
    # the bucket model takes the generic scan (_run_chunked) on every layout
    params = StreamModelParams(load_ports=1, shares=(8.0, 16.0),
                               epoch_cycles=64)
    traces = [gemm_trace(s, ALG1_POLICY) for s in SPECS[:2]]
    cfgs = [get_design("BASE"), get_design("RASA-WLBP"),
            get_design("RASA-DB-WLS")]
    before = probe.COUNTS.copy()
    if layout == "sweep_trace":
        sweep_trace(traces[0], cfgs, params, backend="jax")
        useful, lanes, steps = len(traces[0]) * 3, 4, len(traces[0])
    elif layout == "cores":
        run_cores(traces, cfgs[0], [params, params], backend="jax")
        useful, lanes = sum(len(t) for t in traces), 2
        steps = max(len(t) for t in traces)
    else:
        sweep_traces(traces, cfgs, params, backend="jax")
        useful, lanes = sum(len(t) for t in traces) * 3, 4
        steps = sum(len(t) + 1 for t in traces)     # one marker per GEMM
    got = probe.COUNTS.copy()
    got.subtract(before)
    calls = -(-steps // fastsim.CHUNK)
    assert got["sim.chunk_calls"] == calls
    assert got["sim.scan_steps"] == calls * fastsim.CHUNK * lanes
    assert got["sim.useful_steps"] == useful
    assert got["sim.h2d_bytes"] > 0
