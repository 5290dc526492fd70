"""The readers of the simulator's own spans and counters, on the CPU.

They cover the self-time reduction and the naming of idle gaps on
synthetic nested spans, the readers on a synthetic run and on nothing,
a trace recorded without the probe, a CPU-profiled sweep and a traced
tiny run of the harness that reports every new metric.
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import run, spans  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["chunk_calls_per_q", "scan_pad_share", "h2d_mb_per_q", "lower_share",
       "prep_share", "dispatch_share", "wait_share"]
FIXTURES = ROOT / "perfbench" / "fixtures"

#: one question on one thread: build, then a sweep that lowers, dispatches
#: and waits twice, and reports; times in ns
NESTED = [["question.build", 100, 200, 0, {}],
          ["question.sweep", 200, 1000, 0, {}],
          ["sim.lower", 210, 300, 0, {}],
          ["sim.dispatch", 300, 350, 0, {"sim.chunk_calls": 2,
                                         "sim.h2d_bytes": 10}],
          ["sim.wait", 350, 600, 0, {}],
          ["sim.dispatch", 600, 620, 0, {"sim.chunk_calls": 1,
                                         "sim.h2d_bytes": 5}],
          ["sim.wait", 620, 900, 0, {}],
          ["sim.report", 900, 990, 0, {}]]


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    return tmp_path


def test_readers_read_the_trace_the_harness_writes():
    assert spans.TRACE_DIR == run.TRACE_DIR
    assert {m["name"] for m in BENCH["per_layer"]} >= set(NEW)


def test_self_time_is_the_span_minus_its_children():
    got = spans.self_times(NESTED, 0, 1100)
    assert got == {"question.build": 100, "question.sweep": 20,
                   "sim.lower": 90, "sim.dispatch": 70, "sim.wait": 530,
                   "sim.report": 90}
    # clipped to the window: only what lies inside counts
    got = spans.self_times(NESTED, 150, 400)
    assert got["question.build"] == 50 and got["sim.wait"] == 50
    assert got["question.sweep"] == 10


def test_self_times_of_separate_threads_do_not_nest():
    other = [["sim.lower", 250, 700, 1, {}]]
    got = spans.self_times(NESTED + other, 0, 1100)
    assert got["question.sweep"] == 20 and got["sim.lower"] == 90 + 450


@pytest.mark.parametrize("gap, owner", [
    ((400, 550), "sim.wait"),          # inside sim.wait inside the sweep
    ((320, 400), "sim.wait"),          # mostly waiting
    ((300, 950), "sim.wait"),          # over two dispatches and two waits
    ((190, 260), "sim.lower"),
    ((150, 180), "question.build"),
    ((1000, 1100), spans.IDLE_LABEL),  # after the question
])
def test_a_gap_is_named_by_the_span_innermost_for_most_of_it(gap, owner):
    assert spans.gap_owner(NESTED, *gap) == owner


def test_summary_sums_the_counters_of_the_window():
    got = spans.summary({"window": [0, 1000], "spans": NESTED})
    assert got["counters"] == {"sim.chunk_calls": 3, "sim.h2d_bytes": 15}
    assert got["window_s"] == pytest.approx(1e-6)
    assert got["self_s"]["sim.wait"] == pytest.approx(530e-9)
    # spans that start outside the window are not the window's
    got = spans.summary({"window": [0, 550], "spans": NESTED})
    assert got["counters"] == {"sim.chunk_calls": 2, "sim.h2d_bytes": 10}
    no_probe = [sp for sp in NESTED if sp[0].startswith("question.")]
    assert spans.summary({"window": [0, 1000], "spans": no_probe}) is None
    assert spans.summary({"window": None, "spans": NESTED}) is None


def _synthetic_run():
    got = spans.summary({"window": [0, 1000], "spans": NESTED})
    return {"questions": 1, "trace": None, "compiles_in_window": 0,
            "spans": {k: got[k] for k in ("window_s", "self_s")},
            "counters": {**got["counters"], "sim.scan_steps": 4096,
                         "sim.useful_steps": 1024}}


def test_readers_of_a_synthetic_run():
    r = _synthetic_run()
    got = {n: run._metric_reader(n)(r) for n in NEW}
    assert got == {"chunk_calls_per_q": 3.0, "scan_pad_share": 0.75,
                   "h2d_mb_per_q": pytest.approx(15e-6),
                   "lower_share": pytest.approx(0.09),
                   "prep_share": pytest.approx(0.09),
                   "dispatch_share": pytest.approx(0.07),
                   "wait_share": pytest.approx(0.53)}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("missing", ["counters", "spans", "both"])
def test_readers_return_nothing_without_their_input(name, missing,
                                                    trace_dir):
    r = _synthetic_run()
    if missing == "both":
        del r["counters"], r["spans"]      # and the trace dir is empty
    else:
        r[missing] = None
    source = next(m["source"] for m in BENCH["per_layer"]
                  if m["name"] == name)
    wanted = {"program_counter": "counters", "program_span": "spans"}[source]
    got = run._metric_reader(name)(r)
    assert (got is None) == (missing in (wanted, "both"))


def test_a_trace_without_the_probe_reads_as_nothing(trace_dir):
    # recorded by a harness run of a program that opens no sim.* span
    (trace_dir / "t.xplane.pb").write_bytes(
        (FIXTURES / "cpu_window.xplane.pb").read_bytes())
    ev = spans.load(trace_dir)
    assert ev["window"] is not None and spans.summary(ev) is None
    r = {"questions": 1, "trace": None, "compiles_in_window": 0}
    assert all(run._metric_reader(n)(r) is None for n in NEW)


def test_spans_of_a_profiled_sweep_nest_inside_the_question(tmp_path,
                                                            monkeypatch):
    import jax
    import repro.core as core
    from repro.core import fastsim, probe

    monkeypatch.setattr(fastsim, "CHUNK", 256)

    specs = [core.GemmSpec("a", 64, 128, 96), core.GemmSpec("b", 32, 64, 64)]
    designs = ["BASE", "RASA-WLBP", "RASA-DB-WLS"]
    before = probe.COUNTS.copy()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(spans.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("question.sweep"):
                core.sweep_workload(specs, designs, backend="jax")
    delta = probe.COUNTS.copy()
    delta.subtract(before)
    ev = spans.load(tmp_path)
    (_, q0, q1, _, _), = [sp for sp in ev["spans"]
                          if sp[0] == "question.sweep"]
    sim = [sp for sp in ev["spans"] if sp[0].startswith("sim.")]
    assert {sp[0] for sp in sim} == {"sim.lower", "sim.analyse", "sim.stage",
                                     "sim.dispatch", "sim.wait", "sim.report"}
    assert all(q0 <= s <= e <= q1 for _, s, e, _, _ in sim)
    got = spans.summary(ev)
    assert got["counters"] == {k: v for k, v in delta.items() if v}
    # one load signature, one chunk per GEMM
    assert got["counters"]["sim.chunk_calls"] == len(specs)
    selfs = got["self_s"]
    assert all(v >= 0 for v in selfs.values())
    assert sum(selfs.values()) <= (q1 - q0) / 1e9 + 1e-9


def test_a_traced_tiny_run_reports_every_new_metric(trace_dir, monkeypatch,
                                                    capsys):
    from repro.core import fastsim
    from test_perfbench import _devices, _tiny_cell

    # the tiny layer is below the size at which "fast" takes the jax scan;
    # short chunks keep the scan quick on the CPU
    monkeypatch.setattr(fastsim, "FAST_JAX_MIN_INSTRS", 0)
    monkeypatch.setattr(fastsim, "CHUNK", 256)
    out = run.run_cell(_tiny_cell(), 11, 0.0, True, _devices())
    assert out["correct"] is True
    got = {n: out["metrics"][n]["value"] for n in NEW}
    assert got["chunk_calls_per_q"] >= 1
    assert 0 < got["scan_pad_share"] < 1 and got["h2d_mb_per_q"] > 0
    shares = [got[n] for n in NEW[3:]]
    assert all(v >= 0 for v in shares) and sum(shares) <= 1
    assert spans.main([str(trace_dir)]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["counters"]["sim.chunk_calls"] == got["chunk_calls_per_q"]
    # no device plane on the CPU: the whole window is one gap
    assert len(printed["idle_gaps"]) == 1
