"""The chip benchmark's own tests, on the CPU at small sizes.

They cover the question generator, the plain reference against the
package's oracle and the architecture adapters against the program's
layer compiler, the trace reduction on a recorded trace, the refusal
of a non-TPU platform, the result line's keys, the float32 control and
the faults that must turn ``correct`` false.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import check, control, reference, run, xtrace  # noqa: E402
from perfbench.questions import questions, warmup_question  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
TABLE = json.loads((ROOT / "perfbench" / "designs.json").read_text())
FIXTURES = ROOT / "perfbench" / "fixtures"


def _mix(name: str) -> dict:
    return json.loads((ROOT / "perfbench" / "traffic" / f"{name}.json")
                      .read_text())


def _key(q: dict) -> tuple:
    return (q["batch"], q["seq"], tuple(d["name"] for d in q["designs"]))


def _take(mix: dict, seed: int, n: int, stream: str = "window") -> list:
    qs = questions(mix, TABLE, seed, stream)
    return [_key(next(qs)) for _ in range(n)]


# ------------------------------------------------------------ questions

@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_questions(mix):
    seed = 3_000_000_019
    assert _take(_mix(mix), seed, 30) == _take(_mix(mix), seed, 30)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_draw_different_orders(mix):
    assert _take(_mix(mix), 1, 30) != _take(_mix(mix), 2, 30)


@pytest.mark.parametrize("mix", MIXES)
def test_warmup_is_outside_the_window_list(mix):
    m = _mix(mix)
    warm = warmup_question(m, TABLE, 7)
    assert _key(warm) != _take(m, 7, 1)[0]


def test_prefill_prompts_never_repeat_within_a_block():
    m = _mix("sweep-prefill")
    for seed in (0, 5, 2**31 + 11):
        seqs = [k[1] for k in _take(m, seed, 3 * len(m["seq"]))]
        for b in range(3):
            block = seqs[b * len(m["seq"]):(b + 1) * len(m["seq"])]
            assert sorted(block) == sorted(m["seq"])


def test_search_neighbours_are_single_knob_and_split_six_ways():
    m = _mix("search-decode")
    knobs = ("rows", "cols", "macs_per_pe", "pipe", "wlbp", "wls",
             "double_buffer", "load_latency", "load_ports")
    qs = questions(m, TABLE, 42)
    for _ in range(20):
        q = next(qs)
        base = next(d for d in TABLE if d["name"] == q["designs"][0]["name"]
                    .split("~")[0])
        names = [d["name"] for d in q["designs"]]
        assert len(names) == 8 and len(set(names)) == 8
        for d in q["designs"]:
            changed = {k for k in knobs if d[k] != base[k]}
            assert changed and (changed <= {"rows", "cols", "macs_per_pe"}
                                or changed <= {"pipe", "wlbp", "wls",
                                               "double_buffer"}
                                or len(changed) == 1)
            assert d["rows"] * d["cols"] * d["macs_per_pe"] == 512
            assert not d["wls"] or d["double_buffer"]
        sigs = {(d["load_latency"], d["load_ports"]) for d in q["designs"]}
        assert len(sigs) == 6


# ------------------------------------------------------------ reference

@pytest.mark.parametrize("shape", [(1, 1536, 2560), (16, 512, 1536),
                                   (40, 100, 200), (128, 6144, 64)])
def test_reference_matches_the_package_oracle(shape):
    from repro.core import DESIGNS, GemmSpec, simulate

    for i, (name, cfg) in enumerate(DESIGNS.items()):
        cfg = dataclasses.replace(cfg, load_latency=(2, 5, 10, 20)[i % 4],
                                  load_ports=(1, 2, 4)[i % 3])
        want = simulate(GemmSpec("g", *shape), cfg)
        d = {k: getattr(cfg, k) for k in check.TIMING_KEYS}
        got = reference.simulate(reference.lower(*shape), d)
        assert got["cycles"] == want.cycles
        assert got["utilization"] == want.utilization
        assert [got[k] for k in check.COUNTS] == [
            want.n_mm, want.n_tl, want.n_ts, want.wl_skips]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("batch,seq,phase", [(1, 32, "prefill"),
                                             (3, 128, "decode"),
                                             (8, 128, "decode")])
def test_reference_layer_matches_compile_workload(cell, batch, seq, phase):
    from repro.workload.compile import compile_workload

    c = run.load_cell(cell)
    model, opts = c["arch"].program(c["config"])
    wl = compile_workload(model, batch=batch, seq=seq, phase=phase,
                          options=opts)
    assert [(s.M, s.K, s.N) for s in wl.specs] == [
        g[1:] for g in c["arch"].layer_gemms(c["config"], batch, seq, phase)]


# ------------------------------------------------------------ trace reduction

def test_union_counts_overlaps_once():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]


def test_reduction_of_a_synthetic_trace():
    ev = {"window": [100, 1100],
          "host": [["question.build", 100, 300], ["question.sweep", 300, 1100]],
          "devices": {"/device:TPU:0": {
              "ops": [["scan", 50, 150], ["scan", 400, 600], ["fusion", 500, 700],
                      ["scan", 1000, 1200]],
              "modules": [["jit_sim", 50, 150], ["jit_sim", 400, 700],
                          ["jit_sim", 1000, 1200], ["jit_sim", 1300, 1400]],
              "ops_truncated": False}}}
    r = xtrace.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["launches"] == 3
    assert [n for n, _ in r["device_ops"]] == ["scan", "fusion"]
    assert r["idle_gaps"][0] == ["question.sweep", pytest.approx(300e-9)]
    assert r["idle_gaps"][1] == ["question.build", pytest.approx(250e-9)]


def test_reading_a_recorded_trace_without_a_device_plane(tmp_path):
    # a tiny harness run traced on the CPU: the window and the harness's
    # host spans are there, no TPU plane is, so no device number is read
    (tmp_path / "t.xplane.pb").write_bytes(
        (FIXTURES / "cpu_window.xplane.pb").read_bytes())
    ev = xtrace.load(str(tmp_path))
    w0, w1 = ev["window"]
    assert w1 - w0 == pytest.approx(12435197.0)
    assert [n for n, _, _ in ev["host"]] == ["question.build", "question.sweep"]
    assert all(w0 <= s <= e <= w1 for _, s, e in ev["host"])
    assert ev["devices"] == {} and xtrace.reduce_events(ev) is None
    run_ = {"questions": 1, "trace": None, "compiles_in_window": 0}
    for m in BENCH["per_layer"]:
        if m["source"] == "device_trace":
            assert run._metric_reader(m["name"])(run_) is None


def test_busy_time_falls_back_to_ops_and_truncated_ops_list_programs():
    ops = [["scan", 150, 250], ["scan", 600, 900]]
    ev = {"window": [100, 1100], "host": [],
          "devices": {"/device:TPU:0": {"ops": ops, "modules": [],
                                        "ops_truncated": False}}}
    r = xtrace.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(400e-9) and r["launches"] == 0
    ev["devices"]["/device:TPU:0"] = {
        "ops": ops, "ops_truncated": True,
        "modules": [["jit_sim", 120, 300], ["jit_sim", 550, 950]]}
    r = xtrace.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(580e-9) and r["launches"] == 2
    assert r["device_ops"] == [["jit_sim", pytest.approx(580e-9)]]


def test_readers_return_nothing_without_a_trace():
    r = {"questions": 3, "trace": None, "compiles_in_window": 0}
    for m in BENCH["per_layer"]:
        got = run._metric_reader(m["name"])(r)
        assert got is None or m["source"] != "device_trace"


# ------------------------------------------------------------ the harness

def test_refuses_a_non_tpu_platform(monkeypatch, capsys):
    def boom(*a, **k):
        raise AssertionError("a question ran on a refused platform")

    monkeypatch.setattr(run, "run_cell", boom)
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "refused" in out.err


def _tiny_cell(mix: str = "sweep-decode") -> dict:
    c = run.load_cell("granite-moe-3b-a800m.sweep-decode")
    c["config"] = {**c["config"], "name": "tiny-moe", "hidden_size": 64,
                   "intermediate_size": 32, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "num_local_experts": 8, "num_experts_per_tok": 2}
    c["mix"] = _mix(mix)
    return c


def _devices():
    import jax
    return jax.devices()[:1]


def test_result_line_has_the_contract_keys(capsys):
    c = run.load_cell("granite-moe-3b-a800m.sweep-decode")
    run.emit(run.run_cell(c, 2**31 + 5, 0.0, False, _devices()))
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"sweep_minstr_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == set(check.LIMITS)
    tail = out.err.strip().splitlines()[-len(check.LIMITS):]
    assert all(re.match(r"check \w+: \S+ \(limit \S+\)$", t) for t in tail)


def test_traced_run_reports_only_per_layer_metrics():
    out = run.run_cell(_tiny_cell(), 3, 0.0, True, _devices())
    assert out["correct"] is True and list(out)[-1] == "checks"
    assert set(out["metrics"]) <= {m["name"] for m in BENCH["per_layer"]}
    assert out["metrics"]["compiles_in_window"]["value"] >= 0


def test_control_in_float32_is_not_correct():
    # float32 loses the cycle counts past 2**24; every prefill question
    # has a feed-forward GEMM that passes it under BASE
    c = run.load_cell("qwen3-1.7b.sweep-prefill")
    c["table"] = [d for d in c["table"] if d["name"] == "BASE"]
    res = control.run_control(c, 2**31 + 77, 1, "float32")
    assert res["correct"] is False
    assert res["checks"]["cycles_rel_gap"]["value"] > 1e-4


def test_float32_holds_small_cycle_counts_exactly():
    # why the decode mixes need a control of their own: below 2**20
    # cycles every time is a sixteenth of a cycle that float32 holds
    res = control.run_control(_tiny_cell(), 11, 3, "float32")
    assert res["correct"] is True


@pytest.mark.parametrize("mix", MIXES)
def test_whole_cycle_issue_control_is_not_correct(mix):
    res = control.run_control(_tiny_cell(mix), 11, 2, "whole_cycle_issue")
    assert res["correct"] is False
    assert res["checks"]["cycles_rel_gap"]["value"] > 0


def _fault(kind: str):
    from repro import core

    real = core.sweep_workload

    def broken(specs, cfgs, **kw):
        grid = real(specs, cfgs, **kw)
        if kind == "answer_altered":
            row = grid[int(np.argmax([s.M * s.K * s.N for s in specs]))]
            for name, r in list(row.items()):
                row[name] = dataclasses.replace(r, cycles=r.cycles + 1)
        elif kind == "half_designs_left_out":
            keep = [c.name for c in cfgs[: len(cfgs) // 2]]
            grid = [{n: row[n] for n in keep} for row in grid]
        elif kind == "half_gemms_left_out":
            grid = grid[: len(grid) // 2]
        return grid

    return broken


@pytest.mark.parametrize("kind", ["answer_altered", "half_designs_left_out",
                                  "half_gemms_left_out"])
@pytest.mark.parametrize("mix", MIXES)
def test_a_broken_timed_path_is_not_correct(monkeypatch, kind, mix):
    from repro import core

    monkeypatch.setattr(core, "sweep_workload", _fault(kind))
    out = run.run_cell(_tiny_cell(mix), 9, 0.0, False, _devices())
    assert out["correct"] is False


@pytest.mark.parametrize("bad", range(len(TABLE)))
def test_a_fault_in_one_design_is_caught_by_the_sampled_check(bad):
    c = _tiny_cell("sweep-prefill")
    assert c["mix"]["check_designs_per_gemm"] < len(TABLE)
    answered = control.control_answers(c, 5, 1, "whole_cycle_issue")
    q = answered[0]
    rows = []
    for g in q["gemms"]:
        s = reference.lower(*g[1:])
        rows.append({d["name"]: dict(reference.simulate(s, d))
                     for d in q["designs"]})
    assert check.passed(check.compare([{**q, "results": rows}], c, 5))
    for row in rows:
        row[TABLE[bad]["name"]]["cycles"] += 1
    assert not check.passed(check.compare([{**q, "results": rows}], c, 5))


def test_a_sound_tiny_run_is_correct():
    out = run.run_cell(_tiny_cell(), 9, 0.0, False, _devices())
    assert out["correct"] is True


# ------------------------------------------------------------ BENCHMARK.json

def test_benchmark_json_keys_and_files():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for w in BENCH["workloads"]:
        assert name.match(w["name"]) and w["chips"] in (1, 4)
        assert (ROOT / "perfbench" / "traffic" / f"{w['traffic']}.json").exists()
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert all(k in cfg for k in c["reduced"])
    for m in BENCH["per_layer"]:
        assert (ROOT / "perfbench" / "metrics" / f"{m['name']}.py").exists()
    assert {m["name"] for m in BENCH["end_to_end"]} == {
        "sweep_minstr_per_s", "setup_s"}
