"""Telemetry subsystem tests (``repro.obs``).

Four pillars:

* **Replay parity** -- ``replay_events`` must reproduce the reference
  ``PipelineSimulator`` run event for event: makespan, every MM sub-stage
  window (vs ``keep_schedules``), and every bandwidth grant (vs
  ``EpochBandwidthLoadModel(record_grants=True)``), across all designs.
* **Conservation** -- the five attribution buckets sum exactly to
  ``window x cores`` per core and are non-negative, on closed-batch and
  online runs, on the reference and numpy backends alike; and the two
  backends agree on the bucket totals.
* **Perfetto golden fixture** -- the trace_event JSON of a small skewed
  4-core online run is pinned in ``tests/fixtures/perfetto_skewed4.json``;
  any drift must be a bug or a deliberate regeneration

      PYTHONPATH=src python tests/test_obs.py --regen

* **Plumbing** -- the BENCH envelope validator, the ``load_stall_cycles``
  deprecated alias, the ASCII renderer, the stage-event cap, and the
  telemetry-off default (reports carry ``telemetry=None``).
"""

import json
import pathlib
import sys

import pytest

from repro.core import DESIGNS, TABLE_I, GemmSpec, simulate
from repro.core.designs import get_design
from repro.core.fastsim import StreamModelParams
from repro.core.tiling import ALG1_POLICY, lower_gemm
from repro.core.timing import PipelineSimulator
from repro.core.trace import compile_stream
from repro.multicore import ChipConfig, simulate_chip
from repro.multicore.chip import EpochBandwidthLoadModel
from repro.obs import (TelemetryConfig, render_timeline, replay_events,
                       to_trace_events)
from repro.obs.attribution import BUCKETS, simreport_attribution
from repro.serving.simbatch import run_batcher, skewed_trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
REL = 1e-6

#: share schedule tight enough to throttle every design's load stream
SHARES = tuple([4.0, 8.0, 16.0, 6.0] * 8)
EPOCH = 512.0
TAIL = 32.0
BURST = 2048.0

#: skewed 4-GEMM layer workload for the closed-batch conservation tests
CLOSED_WORKLOAD = [TABLE_I["DLRM-2"], TABLE_I["BERT-1"],
                   TABLE_I["DLRM-2"], TABLE_I["DLRM-2"]]


def _stream():
    return list(lower_gemm(GemmSpec("obs", 64, 256, 256), ALG1_POLICY))


# ------------------------------------------------------- replay parity
@pytest.mark.parametrize("design", sorted(DESIGNS))
def test_replay_matches_reference(design):
    """The post-hoc event replay reproduces the reference simulator's
    makespan, MM sub-stage schedule, and grant-for-grant arbiter timing
    under a throttling share schedule."""
    cfg = get_design(design)
    stream = _stream()
    model = EpochBandwidthLoadModel(
        cfg.load_ports, SHARES, EPOCH, TAIL, burst_bytes=BURST,
        store_ports=cfg.store_ports, charge_store_bytes=True,
        record_grants=True)
    ref = PipelineSimulator(cfg, keep_schedules=True,
                            load_model=model).run(stream)
    params = StreamModelParams(cfg.load_ports, cfg.store_ports, SHARES,
                               EPOCH, TAIL, BURST, True)
    ev = replay_events(compile_stream(stream), cfg, params)

    assert ev.cycles == pytest.approx(ref.cycles, rel=REL)
    assert ev.bw_stall == pytest.approx(ref.bw_stall_cycles, rel=REL,
                                        abs=1e-6)
    assert ev.wl_skips == ref.wl_skips
    assert len(ev.mm_index) == ref.n_mm
    assert len(ev.tl_index) == ref.n_tl
    assert len(ev.ts_index) == ref.n_ts

    # MM sub-stages vs the reference keep_schedules log
    assert len(ref.schedules) == ref.n_mm
    for k, sch in enumerate(ref.schedules):
        assert int(ev.mm_index[k]) == sch.index
        assert bool(ev.mm_skip[k]) == sch.wl_skipped
        got = (ev.mm_wl_start[k], ev.mm_ff_start[k], ev.mm_ff_end[k],
               ev.mm_fs_end[k], ev.mm_dr_end[k])
        want = (sch.wl_start, sch.ff_start, sch.ff_end, sch.fs_end,
                sch.dr_end)
        assert got == pytest.approx(want, rel=REL, abs=1e-9), sch.index

    # grant-for-grant: charged accesses (loads + stores) in issue order
    replayed = sorted(
        [(int(i), float(s)) for i, s in zip(ev.tl_index, ev.tl_start)]
        + [(int(i), float(s)) for i, s in zip(ev.ts_index, ev.ts_start)])
    assert len(replayed) == len(model.grants)
    for (_, start), (g_start, _) in zip(replayed, model.grants):
        assert start == pytest.approx(g_start, rel=REL, abs=1e-9)


# -------------------------------------------------------- conservation
def _assert_conserved(att, window, n_cores):
    assert att is not None
    assert len(att.cores) == n_cores
    assert att.window == pytest.approx(window, rel=1e-9)
    for c in att.cores:
        for b in BUCKETS:
            assert getattr(c, b) >= -1e-6, (c.core, b)
        assert c.total == pytest.approx(window, rel=1e-9, abs=1e-6), c.core
    total = sum(att.total(b) for b in BUCKETS)
    assert total == pytest.approx(att.occupied_cycles, rel=1e-9, abs=1e-6)
    assert sum(att.fractions().values()) == pytest.approx(1.0, abs=1e-9)


def test_closed_chip_conservation_cross_backend():
    """Closed-batch buckets conserve per core on both backends, the
    backends agree on every bucket total, and the stages-on replay does
    not diverge (``build_chip_telemetry`` raises if it does)."""
    tcfg = TelemetryConfig(enabled=True, stages=True)
    reps = {be: simulate_chip(CLOSED_WORKLOAD,
                              ChipConfig(n_cores=4, design="RASA-WLBP",
                                         bw_bytes_per_cycle=32.0,
                                         backend=be),
                              scheduler="lpt", telemetry=tcfg)
            for be in ("reference", "numpy")}
    for be, rep in reps.items():
        assert rep.telemetry is not None, be
        _assert_conserved(rep.telemetry.attribution, rep.cycles, 4)
    ref, fast = reps["reference"], reps["numpy"]
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL)
    for b in BUCKETS:
        assert fast.telemetry.attribution.total(b) == pytest.approx(
            ref.telemetry.attribution.total(b), rel=REL, abs=1e-3), b


def test_online_conservation_cross_backend():
    """Online (serving) buckets conserve on both backends and agree."""
    requests = skewed_trace(d_model=256, heavy_prompt=256, n_light=6)
    tcfg = TelemetryConfig(enabled=True, stages=True)
    # the fixed policy round-robins blindly, so light requests queue
    # behind the heavy prefills and the queue_wait bucket must trigger
    reps = {be: run_batcher(requests,
                            ChipConfig(n_cores=4, design="RASA-WLBP",
                                       bw_bytes_per_cycle=64.0, backend=be),
                            policy="fixed", telemetry=tcfg)
            for be in ("reference", "numpy")}
    for be, rep in reps.items():
        tele = rep.telemetry
        assert tele is not None and tele.kind == "online", be
        assert len(tele.segments) == len(requests), be
        _assert_conserved(rep.attribution, tele.window, 4)
        assert rep.attribution.total("queue_wait") > 0.0, be
    ref, fast = reps["reference"], reps["numpy"]
    for b in BUCKETS:
        assert fast.attribution.total(b) == pytest.approx(
            ref.attribution.total(b), rel=REL, abs=1e-3), b


def test_simreport_attribution_degenerate_form():
    """Single-engine split: window == cycles, idle == 0, fractions sum
    to one, and compute matches the lowered workload."""
    spec = TABLE_I["DLRM-2"]
    res = simulate(spec, "RASA-DMDB-WLS")
    att = simreport_attribution([spec], ALG1_POLICY, res.cycles)
    _assert_conserved(att, res.cycles, 1)
    (core,) = att.cores
    assert core.queue_wait == 0.0 and core.idle == 0.0
    assert 0.0 < core.compute <= res.cycles


# ------------------------------------------------ Perfetto golden trace
def _golden_telemetry():
    """Small skewed 4-core online run (numpy backend for determinism)."""
    requests = skewed_trace(d_model=128, heavy_prompt=256, light_prompt=32,
                            n_heavy=2, n_light=4)
    rep = run_batcher(requests,
                      ChipConfig(n_cores=4, design="RASA-WLBP",
                                 bw_bytes_per_cycle=32.0, backend="numpy"),
                      policy="occupancy",
                      telemetry=TelemetryConfig(enabled=True))
    return rep.telemetry


def _assert_trace_close(fixture, fresh, path="trace"):
    assert type(fixture) is type(fresh) or (
        isinstance(fixture, (int, float)) and isinstance(fresh, (int, float))
    ), f"{path}: type drift {type(fixture).__name__} != {type(fresh).__name__}"
    if isinstance(fixture, dict):
        assert fixture.keys() == fresh.keys(), \
            f"{path}: key drift {sorted(fixture)} != {sorted(fresh)}"
        for k in fixture:
            _assert_trace_close(fixture[k], fresh[k], f"{path}/{k}")
    elif isinstance(fixture, list):
        assert len(fixture) == len(fresh), \
            f"{path}: length drift {len(fixture)} != {len(fresh)}"
        for i, (a, b) in enumerate(zip(fixture, fresh)):
            _assert_trace_close(a, b, f"{path}[{i}]")
    elif isinstance(fixture, bool) or not isinstance(fixture, (int, float)):
        assert fixture == fresh, f"{path}: {fixture!r} != {fresh!r}"
    else:
        assert fresh == pytest.approx(fixture, rel=REL, abs=1e-6), \
            f"{path}: golden {fixture} != recomputed {fresh}"


def test_perfetto_golden_fixture():
    """The exporter's trace_event JSON for the small skewed 4-core online
    run is pinned: event set, timestamps, args and metadata."""
    p = FIXTURES / "perfetto_skewed4.json"
    assert p.exists(), (f"missing fixture {p}; regenerate with "
                        f"`python tests/test_obs.py --regen`")
    fresh = to_trace_events(_golden_telemetry())
    _assert_trace_close(json.loads(p.read_text()), fresh)


def test_trace_events_well_formed():
    """Every exported event is a dict with a phase; the document carries
    the schema marker and a conserving attribution block."""
    doc = to_trace_events(_golden_telemetry())
    events = doc["traceEvents"]
    assert events and all(isinstance(e, dict) and "ph" in e for e in events)
    phases = {e["ph"] for e in events}
    assert {"M", "X", "b", "e", "C", "i"} <= phases
    other = doc["otherData"]
    assert other["schema"] == "rasa-trace/1"
    att = other["attribution"]
    assert sum(att.values()) == pytest.approx(
        other["window_cycles"] * other["n_cores"], rel=1e-9, abs=1e-6)


def test_stage_event_cap():
    """``max_stage_events`` bounds the export; the overflow is reported
    in the trace metadata instead of silently dropped."""
    tcfg = TelemetryConfig(enabled=True, stages=True, max_stage_events=16)
    rep = simulate_chip(GemmSpec("cap", 64, 256, 256),
                        ChipConfig(n_cores=2, design="RASA-WLBP",
                                   bw_bytes_per_cycle=32.0),
                        telemetry=tcfg)
    doc = to_trace_events(rep.telemetry)
    staged = [e for e in doc["traceEvents"]
              if e.get("cat") in ("stage", "mem", "stall")]
    assert len(staged) <= 16
    assert doc["otherData"]["stage_events_dropped"] > 0


# --------------------------------------------------------- plumbing
def _bench_common():
    sys.path.insert(0, str(pathlib.Path(__file__).parents[1] / "benchmarks"))
    import common
    return common


def test_bench_envelope_validation(tmp_path):
    """``write_bench``-shaped files pass ``validate_bench``; tampered
    schema, missing keys and filename mismatches are each reported."""
    common = _bench_common()
    env = common.bench_envelope("foo", backend="fast")
    assert env["schema"] == common.BENCH_SCHEMA
    env["data"] = {"x": 1}
    good = tmp_path / "BENCH_foo.json"
    good.write_text(json.dumps(env))
    assert common.validate_bench(good) == []

    bad_schema = tmp_path / "BENCH_bar.json"
    bad_schema.write_text(json.dumps(
        dict(env, benchmark="bar", schema="rasa-bench/0")))
    assert any("schema" in e for e in common.validate_bench(bad_schema))

    incomplete = dict(env)
    del incomplete["git_rev"]
    missing = tmp_path / "BENCH_foo2.json"
    missing.write_text(json.dumps(dict(incomplete, benchmark="foo2")))
    assert any("git_rev" in e for e in common.validate_bench(missing))

    misnamed = tmp_path / "BENCH_other.json"
    misnamed.write_text(json.dumps(env))      # says "foo", named "other"
    assert any("does not match filename" in e
               for e in common.validate_bench(misnamed))

    broken = tmp_path / "BENCH_broken.json"
    broken.write_text("{not json")
    assert any("unreadable" in e for e in common.validate_bench(broken))


def test_cache_json_keyed_on_device(tmp_path, monkeypatch):
    """A cached benchmark result is served only on the platform and device
    kind it was computed on: a CPU result copied with the tree is
    recomputed on the chip."""
    common = _bench_common()
    monkeypatch.setattr(common, "RESULTS", tmp_path)
    calls = []

    def compute():
        calls.append(1)
        return {"n": len(calls)}

    assert common.cache_json("k", compute, fingerprint="f") == {"n": 1}
    assert common.cache_json("k", compute, fingerprint="f") == {"n": 1}
    assert common.cache_json("k", compute, fingerprint="g") == {"n": 2}
    monkeypatch.setattr(common, "device_key", lambda: "tpu/TPU v5 lite")
    assert common.cache_json("k", compute, fingerprint="g") == {"n": 3}
    assert len(calls) == 3


def test_load_stall_cycles_deprecated_alias():
    """The pre-PR-6 name keeps working on both result types."""
    res = simulate(GemmSpec("alias", 32, 128, 128), "RASA-WLBP")
    assert res.load_stall_cycles == res.bw_stall_cycles
    cfg = get_design("BASE")
    tr = PipelineSimulator(cfg).run(_stream())
    assert tr.load_stall_cycles == tr.bw_stall_cycles


def test_render_timeline_smoke():
    """The ASCII renderer shows one bar per core, the legend, and the
    attribution table."""
    out = render_timeline(_golden_telemetry(), width=60)
    lines = out.splitlines()
    assert sum(1 for ln in lines if ln.startswith("core ")) == 4
    assert "#" in out and "compute" in out and "fill/drain" in out
    bars = [ln for ln in lines if ln.startswith("core ")]
    assert all(len(ln) == len(bars[0]) for ln in bars)


def test_telemetry_off_by_default():
    """Without opt-in, reports carry no telemetry object (and the serving
    report's attribution shortcut is None)."""
    rep = simulate_chip(CLOSED_WORKLOAD,
                        ChipConfig(n_cores=2, design="RASA-WLBP"),
                        scheduler="lpt")
    assert rep.telemetry is None
    brep = run_batcher(skewed_trace(d_model=128, heavy_prompt=128,
                                    n_light=2),
                       ChipConfig(n_cores=2, design="RASA-WLBP"),
                       policy="occupancy")
    assert brep.telemetry is None and brep.attribution is None


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="recompute and overwrite the Perfetto fixture")
    if not ap.parse_args().regen:
        ap.error("run under pytest, or pass --regen to rebuild fixtures")
    FIXTURES.mkdir(exist_ok=True)
    doc = to_trace_events(_golden_telemetry())
    (FIXTURES / "perfetto_skewed4.json").write_text(
        json.dumps(doc, indent=1, sort_keys=True))
    print(f"wrote perfetto_skewed4.json ({len(doc['traceEvents'])} events)",
          file=sys.stderr)
