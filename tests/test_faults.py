"""Fault-injection & graceful-degradation tests: FaultPlan validation and
zero-cost-off identity, deterministic preemption/migration on both chip
clients, cross-backend parity under faults (deterministic scenarios plus a
hypothesis property over seeded random plans), six-bucket attribution
conservation, mid-fault snapshot/restore, deadline/retry/abandonment
accounting, and the phase-aware / degraded admission policy pins."""

import dataclasses
import math
import pickle

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import ALG1_POLICY, GemmSpec, TABLE_I
from repro.core.fastsim import completed_prefix
from repro.core.tiling import lower_gemm
from repro.core.trace import compile_stream, slice_trace
from repro.multicore import (EMPTY_PLAN, ChipConfig, FaultEvent, FaultPlan,
                             OnlineChip, bw_derate, core_down, core_up,
                             random_plan, simulate_chip, slow_core)
from repro.multicore.chip import stream_model_params
from repro.obs import TelemetryConfig
from repro.obs.attribution import BUCKETS
from repro.serving.simbatch import (ServeRequest, run_batcher, skewed_trace,
                                    synthetic_trace)

REL = 1e-6

#: the closed-batch fault workload (4 Table-I GEMMs over 2 cores)
CLOSED_WORKLOAD = [TABLE_I["DLRM-2"], TABLE_I["BERT-1"], TABLE_I["DLRM-2"],
                   TABLE_I["DLRM-2"]]
CLOSED_KW = dict(n_cores=2, design="RASA-WLBP", bw_bytes_per_cycle=32.0,
                 backend="numpy")

#: the serving fault scenario: mini skewed trace + a down window and a
#: thermal derate placed inside its ~190-epoch busy window
SERVE_KW = dict(n_cores=4, design="RASA-WLBP", bw_bytes_per_cycle=64.0)
SERVE_PLAN = FaultPlan((core_down(0, 3), core_up(0, 30),
                        bw_derate(0.7, 5, 20)))


def _mini_skew():
    return skewed_trace(d_model=256, heavy_prompt=256, n_light=6)


def _heavy(name, epoch, d=256):
    """A prefill-heavy request (prefill is ~94% of its MACs)."""
    return ServeRequest(
        name, epoch, GemmSpec(f"{name}.pf", M=256, K=d, N=d),
        tuple(GemmSpec(f"{name}.d{j}", M=8, K=d, N=d) for j in range(2)))


def _light(name, epoch, d=256):
    """A decode-heavy request (decode is 3/4 of its MACs)."""
    return ServeRequest(
        name, epoch, GemmSpec(f"{name}.pf", M=16, K=d, N=d),
        tuple(GemmSpec(f"{name}.d{j}", M=8, K=d, N=d) for j in range(6)))


def _same_outcome(a, b):
    """Equal BatchReports up to the policy label."""
    return dataclasses.replace(a, policy=b.policy) == b


# ------------------------------------------------------------ validation
def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent("meltdown", 0)
    with pytest.raises(ValueError, match="epoch must be >= 0"):
        FaultEvent("bw_derate", -1, factor=0.5, until=4)
    with pytest.raises(ValueError, match="needs a core index"):
        FaultEvent("core_down", 3)
    with pytest.raises(ValueError, match=r"factor must be in \(0, 1\]"):
        bw_derate(0.0, 1, 4)
    with pytest.raises(ValueError, match=r"factor must be in \(0, 1\]"):
        slow_core(0, 1.5)
    with pytest.raises(ValueError, match="pass until"):
        FaultEvent("bw_derate", 1, factor=0.5)
    with pytest.raises(ValueError, match="must be > "):
        bw_derate(0.5, 4, 4)


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown preemption policy"):
        FaultPlan((core_down(0, 1),), preemption="teleport")
    # the plan only attaches to the epoch arbiter
    with pytest.raises(ValueError, match="requires arbitration='epoch'"):
        ChipConfig(n_cores=2, fault_plan=FaultPlan((core_down(0, 1),)),
                   arbitration="static")
    # events must name cores that exist
    with pytest.raises(ValueError, match="on a 2-core chip"):
        ChipConfig(n_cores=2, fault_plan=FaultPlan((core_down(5, 1),)))


def test_empty_plan_normalizes_to_none():
    """``FaultPlan()`` is the no-op plan: ChipConfig folds it to ``None``,
    so an empty-plan chip config *is* the fault-free config (zero-cost
    off by construction)."""
    assert EMPTY_PLAN.is_empty
    chip = ChipConfig(n_cores=2, fault_plan=FaultPlan())
    assert chip.fault_plan is None
    assert chip == ChipConfig(n_cores=2)


def test_random_plan_seed_determinism():
    kw = dict(horizon=64, n_core_faults=2, down_epochs=8, n_derates=1,
              derate_factor=0.5, derate_epochs=8)
    assert random_plan(4, seed=7, **kw) == random_plan(4, seed=7, **kw)
    assert random_plan(4, seed=7, **kw) != random_plan(4, seed=8, **kw)
    plan = random_plan(4, seed=7, **kw)
    assert plan.has_core_events and plan.needs_online
    assert sum(e.kind == "bw_derate" for e in plan.events) == 1


# ------------------------------------------- preemption cut primitives
def test_slice_trace_matches_compile_stream():
    """``slice_trace(trace, k)`` must equal ``compile_stream(stream[k:])``
    field for field, at every cut -- the preemption remainder is a fresh
    lowering, just cheaper."""
    stream = tuple(lower_gemm(GemmSpec("cut", 96, 256, 256), ALG1_POLICY))
    trace = compile_stream(stream)
    for k in (0, 1, 7, len(stream) // 2, len(stream) - 1, len(stream)):
        got = slice_trace(trace, k)
        want = compile_stream(stream[k:])
        for f in ("opcode", "r_dst", "r_a", "r_b", "nbytes", "tm", "macs",
                  "reusable"):
            assert (getattr(got, f) == getattr(want, f)).all(), (k, f)
        assert (got.n_tl, got.n_ts, got.n_mm) == \
            (want.n_tl, want.n_ts, want.n_mm), k
        assert got.useful_macs == want.useful_macs, k
    with pytest.raises(ValueError, match="out of range"):
        slice_trace(trace, len(stream) + 1)


def test_completed_prefix_monotone_and_bounded():
    """The deterministic preemption cut: 0 instructions at limit 0, the
    whole trace once the limit passes its solo runtime, and monotone
    non-decreasing in between."""
    chip = ChipConfig(n_cores=1, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0)
    engine = chip.core_specs[0].engine
    trace = compile_stream(lower_gemm(GemmSpec("pfx", 64, 256, 256),
                                      chip.core_specs[0].policy))
    params = stream_model_params(chip, engine)
    assert completed_prefix(trace, engine, params, 0.0) == 0
    assert completed_prefix(trace, engine, params, math.inf) == len(trace)
    last = 0
    for limit in (100.0, 1000.0, 5000.0, 20000.0, 1e6):
        k = completed_prefix(trace, engine, params, limit)
        assert last <= k <= len(trace)
        last = k
    assert last == len(trace)


def test_two_preemptions_resume_from_checkpoints(monkeypatch):
    """Repeated ``core_down`` preemptions of one segment replay only the
    work past its latest valid snapshot when computing the cut, never the
    whole history.  Pre-fix, every preemption's ``completed_prefix`` cut
    replayed the segment from instruction 0 -- up to a full snapshot
    stride of re-simulation per preemption, compounding across repeated
    outages of the same logical segment."""
    import repro.multicore.online as online_mod
    stride = 64
    spec = GemmSpec("long", 128, 256, 256)
    kw = dict(n_cores=2, design="RASA-WLBP", bw_bytes_per_cycle=16.0,
              backend="fast")

    clean = OnlineChip(ChipConfig(**kw), snap_stride=stride)
    h = clean.submit(0, [spec])
    clean.drain()
    F = math.ceil(clean.finish_time(h) / clean.chip.epoch_cycles)
    assert F >= 9            # room for two mid-flight outages

    plan = FaultPlan((core_down(0, F // 3), core_up(0, F // 3 + 1),
                      core_down(1, 2 * F // 3), core_up(1, 2 * F // 3 + 1)))
    cuts, replays = [], []
    orig = online_mod.completed_prefix

    def spy(trace, cfg, params, limit, *args, **kwargs):
        carry = kwargs.get("carry", args[0] if args else None)
        n = orig(trace, cfg, params, limit, *args, **kwargs)
        cuts.append(n)
        replays.append(n - (carry.i if carry is not None else 0))
        return n

    monkeypatch.setattr(online_mod, "completed_prefix", spy)
    sim = OnlineChip(ChipConfig(fault_plan=plan, **kw), snap_stride=stride)
    sim.submit(0, [spec])
    sim.drain()
    assert sim.n_preempted == 2 and len(cuts) == 2
    # meaningful scenario: each cut lands well past the first checkpoints
    assert all(n > 2 * stride for n in cuts)
    # the fix: each replay covers at most the tail past the last snapshot
    assert all(r <= 2 * stride for r in replays)
    assert sim.stats.get("preempt_replay_instrs") == sum(replays)


# --------------------------------------------- closed-batch fault client
def test_core_down_preempts_migrates_and_logs():
    plan = FaultPlan((core_down(0, 2), core_up(0, 12)))
    base = simulate_chip(CLOSED_WORKLOAD, ChipConfig(**CLOSED_KW),
                         scheduler="lpt")
    rep = simulate_chip(CLOSED_WORKLOAD,
                        ChipConfig(fault_plan=plan, **CLOSED_KW),
                        scheduler="lpt")
    assert rep.n_preemptions >= 1
    assert rep.n_migrations >= 1
    assert rep.cycles > base.cycles          # the outage costs wall-clock
    assert rep.fault_lost_cycles > 0.0
    assert rep.fault_log == ((2, "core0 down"), (12, "core0 up"))
    assert rep.macs == base.macs             # no work lost from the answer


def test_restart_preemption_loses_at_least_resume():
    """``restart`` discards the checkpointed prefix ``resume`` keeps: with
    a late outage it must lose strictly more work and finish no earlier."""
    reps = {}
    for prem in ("resume", "restart"):
        plan = FaultPlan((core_down(0, 300), core_up(0, 500)),
                         preemption=prem)
        reps[prem] = simulate_chip(CLOSED_WORKLOAD,
                                   ChipConfig(fault_plan=plan, **CLOSED_KW),
                                   scheduler="lpt")
    assert reps["restart"].fault_lost_cycles > \
        reps["resume"].fault_lost_cycles
    assert reps["restart"].cycles >= reps["resume"].cycles


def test_bw_derate_and_slow_core_closed_batch():
    """Windowed thermal derate and DVFS throttle both cost cycles on the
    closed path (no core events -> no preemption machinery involved)."""
    base = simulate_chip(CLOSED_WORKLOAD, ChipConfig(**CLOSED_KW),
                         scheduler="lpt")
    derate = simulate_chip(
        CLOSED_WORKLOAD,
        ChipConfig(fault_plan=FaultPlan((bw_derate(0.5, 0, 10),)),
                   **CLOSED_KW), scheduler="lpt")
    slow = simulate_chip(
        CLOSED_WORKLOAD,
        ChipConfig(fault_plan=FaultPlan((slow_core(0, 0.5),)),
                   **CLOSED_KW), scheduler="lpt")
    assert derate.cycles > base.cycles
    assert slow.cycles > base.cycles
    assert derate.n_preemptions == slow.n_preemptions == 0
    # the derate window scales the arbiter budget epoch by epoch
    plan = FaultPlan((bw_derate(0.5, 2, 4), bw_derate(0.5, 3, 5)))
    assert plan.budget_factors() == (1.0, 1.0, 0.5, 0.25, 0.5)


# ------------------------------------------------- cross-backend parity
@pytest.mark.parametrize("policy", ["occupancy", "degraded"])
def test_fault_backend_parity(policy):
    """Identical fault-run outcomes on the reference, fast and numpy
    backends: the preemption cut and every downstream decision epoch are
    replayed bit-identically."""
    requests = _mini_skew()
    reps = {be: run_batcher(requests,
                            ChipConfig(backend=be, fault_plan=SERVE_PLAN,
                                       **SERVE_KW),
                            policy=policy, snap_stride=512)
            for be in ("reference", "fast", "numpy")}
    ref = reps["reference"]
    for be in ("fast", "numpy"):
        rep = reps[be]
        assert rep.makespan == pytest.approx(ref.makespan, rel=REL), be
        assert rep.finish_times == pytest.approx(ref.finish_times,
                                                 rel=REL), be
        assert rep.latencies == pytest.approx(ref.latencies, rel=REL), be
        assert rep.admit_epochs == ref.admit_epochs, be
        assert (rep.retries, rep.abandoned) == \
            (ref.retries, ref.abandoned), be


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 9), down=st.integers(1, 24),
       n_derates=st.integers(0, 2),
       preemption=st.sampled_from(("resume", "restart")))
def test_random_fault_plans_backend_parity(seed, down, n_derates,
                                           preemption):
    """Hypothesis property: any seeded random FaultPlan produces the same
    BatchReport on the fast and numpy backends -- fault handling never
    introduces backend-dependent behavior."""
    plan = random_plan(2, seed=seed, horizon=40, n_core_faults=1,
                       down_epochs=down, n_derates=n_derates,
                       derate_factor=0.7, derate_epochs=6,
                       preemption=preemption)
    requests = synthetic_trace(4, seed=seed % 97, mean_gap=2, d_model=128,
                               prompt_lens=(16, 32), decode_steps=(1, 2))
    reps = {be: run_batcher(requests,
                            ChipConfig(n_cores=2, design="RASA-WLBP",
                                       bw_bytes_per_cycle=32.0, backend=be,
                                       fault_plan=plan),
                            policy="occupancy", snap_stride=256)
            for be in ("fast", "numpy")}
    fast, np_ = reps["fast"], reps["numpy"]
    assert fast.makespan == pytest.approx(np_.makespan, rel=REL)
    assert fast.finish_times == pytest.approx(np_.finish_times, rel=REL)
    assert fast.admit_epochs == np_.admit_epochs
    assert fast.macs == np_.macs == sum(r.macs for r in requests)


def test_zero_event_plan_serving_bit_identical():
    """Zero-cost off on the serving path: no deadlines + an empty plan +
    the pre-existing policies -> the BatchReport is *equal* to one from a
    build that never heard of faults (the new report fields sit at their
    inert defaults)."""
    requests = _mini_skew()
    plain = run_batcher(requests, ChipConfig(**SERVE_KW),
                        policy="occupancy")
    empty = run_batcher(requests,
                        ChipConfig(fault_plan=FaultPlan(), **SERVE_KW),
                        policy="occupancy", max_attempts=5,
                        backoff_epochs=3)   # inert without deadlines
    assert plain == empty
    assert plain.deadline_miss_rate == 0.0
    assert plain.retries == plain.abandoned == 0
    assert plain.served_macs == plain.macs
    assert plain.goodput_macs_per_cycle == \
        pytest.approx(plain.throughput_macs_per_cycle, rel=1e-12)


# -------------------------------------------------- bucket conservation
def _assert_six_bucket_conserved(att, window, n_cores):
    assert att is not None
    assert set(BUCKETS) == {"compute", "fill_drain", "bw_stall",
                            "fault_lost", "queue_wait", "idle"}
    assert att.window == pytest.approx(window, rel=1e-9)
    for c in att.cores:
        for b in BUCKETS:
            assert getattr(c, b) >= -1e-6, (c.core, b)
        assert c.total == pytest.approx(window, rel=1e-9, abs=1e-6), c.core
    total = sum(att.total(b) for b in BUCKETS)
    assert total == pytest.approx(window * n_cores, rel=1e-9, abs=1e-6)


def test_closed_fault_conservation_cross_backend():
    tcfg = TelemetryConfig(enabled=True)
    plan = FaultPlan((core_down(0, 2), core_up(0, 12)))
    reps = {be: simulate_chip(CLOSED_WORKLOAD,
                              ChipConfig(**{**CLOSED_KW, "backend": be,
                                            "fault_plan": plan}),
                              scheduler="lpt", telemetry=tcfg)
            for be in ("reference", "numpy")}
    for be, rep in reps.items():
        att = rep.telemetry.attribution
        _assert_six_bucket_conserved(att, rep.cycles, 2)
        assert att.total("fault_lost") == \
            pytest.approx(rep.fault_lost_cycles, rel=REL), be
        assert att.total("fault_lost") > 0.0, be
    for b in BUCKETS:
        assert reps["numpy"].telemetry.attribution.total(b) == pytest.approx(
            reps["reference"].telemetry.attribution.total(b),
            rel=REL, abs=1e-3), b


def test_online_fault_conservation_cross_backend():
    tcfg = TelemetryConfig(enabled=True)
    requests = _mini_skew()
    reps = {be: run_batcher(requests,
                            ChipConfig(backend=be, fault_plan=SERVE_PLAN,
                                       **SERVE_KW),
                            policy="occupancy", snap_stride=512,
                            telemetry=tcfg)
            for be in ("reference", "numpy")}
    for be, rep in reps.items():
        _assert_six_bucket_conserved(rep.attribution,
                                     rep.telemetry.window, 4)
        assert rep.attribution.total("fault_lost") > 0.0, be
        # the fault instants surface as labeled marks for the exporters
        labels = [m[1] for m in rep.telemetry.marks]
        assert "core0 down" in labels and "core0 up" in labels, be
    for b in BUCKETS:
        assert reps["numpy"].attribution.total(b) == pytest.approx(
            reps["reference"].attribution.total(b), rel=REL, abs=1e-3), b


# ----------------------------------------------- snapshot mid-fault-run
def test_snapshot_restore_mid_fault_bit_identical():
    """Checkpoint *inside* the down window (after a preemption, with the
    resume chain live), pickle round-trip, restore, finish: bit-identical
    to the uninterrupted run."""
    requests = _mini_skew()
    chip = ChipConfig(backend="fast", fault_plan=SERVE_PLAN, **SERVE_KW)

    def drive(sim):
        for i, r in enumerate(requests):
            if r.arrival_epoch > sim.epoch:
                sim.advance_to(r.arrival_epoch)
            sim.submit(i % 4, r.specs)

    straight = OnlineChip(chip, snap_stride=512)
    drive(straight)
    straight.drain()

    sim = OnlineChip(chip, snap_stride=512)
    drive(sim)
    sim.advance_to(10)                       # inside the [3, 30) outage
    assert sim.n_preempted >= 1
    assert sim.down_cores == (True, False, False, False)
    blob = pickle.dumps(sim.snapshot())
    resumed = OnlineChip.restore(pickle.loads(blob))
    del sim
    resumed.drain()

    assert resumed.makespan == straight.makespan
    assert resumed.share_trace == straight.share_trace
    assert resumed.active_trace == straight.active_trace
    assert resumed.n_retired == straight.n_retired
    assert resumed.n_preempted == straight.n_preempted
    assert resumed.fault_log == straight.fault_log


# ------------------------------------- deadlines, retry and abandonment
def test_deadline_retry_then_abandon_accounting():
    """A request that can never be admitted before its per-attempt
    deadline retries with backoff, then is abandoned: infinite latency,
    excluded from the makespan, counted in the miss rate and excluded
    from goodput."""
    d = 256
    big = ServeRequest("big", 0, GemmSpec("big.pf", M=512, K=d, N=d))
    small = ServeRequest("small", 1, GemmSpec("s.pf", M=16, K=d, N=d),
                         deadline=2048.0)
    chip = ChipConfig(n_cores=1, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0, backend="fast")
    rep = run_batcher((big, small), chip, policy="occupancy",
                      max_attempts=2, backoff_epochs=1)
    assert rep.retries == 1                     # one backoff re-arrival
    assert rep.abandoned == 1
    assert rep.deadline_miss_rate == pytest.approx(0.5)
    assert math.isinf(rep.latencies[1]) and math.isinf(rep.finish_times[1])
    assert rep.makespan == rep.finish_times[0]  # abandoned never extends it
    assert rep.served_macs == big.macs
    assert rep.goodput_macs_per_cycle < rep.throughput_macs_per_cycle
    assert rep.admit_epochs[1] == -1            # never entered the chip


def test_admitted_request_runs_to_completion_late():
    """An admitted request is never killed: finishing past its deadline is
    a miss (zero goodput) but still a served, finite-latency request."""
    late = ServeRequest("late", 0,
                        GemmSpec("late.pf", M=64, K=256, N=256),
                        deadline=1.0)
    chip = ChipConfig(n_cores=1, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0, backend="fast")
    rep = run_batcher((late,), chip, policy="occupancy")
    assert rep.retries == rep.abandoned == 0
    assert rep.deadline_miss_rate == 1.0
    assert rep.served_macs == 0
    assert not math.isinf(rep.latencies[0])


def test_batcher_knob_validation():
    reqs = (_light("l0", 0),)
    chip = ChipConfig(n_cores=1, backend="fast")
    with pytest.raises(ValueError, match="max_attempts"):
        run_batcher(reqs, chip, max_attempts=0)
    with pytest.raises(ValueError, match="backoff_epochs"):
        run_batcher(reqs, chip, backoff_epochs=-1)
    with pytest.raises(ValueError, match="max_prefills"):
        run_batcher(reqs, chip, max_prefills=0)


# ------------------------------------------- degradation policy behavior
def test_degraded_sheds_prefill_when_core_down():
    """Under an outage the degraded policy holds prefill-heavy work back
    and lets later decode-heavy requests queue-jump; healthy it is exactly
    ``occupancy``."""
    reqs = (_light("l0", 0), _heavy("h0", 2), _light("l1", 3),
            _light("l2", 4))
    plan = FaultPlan((core_down(0, 1), core_up(0, 200)))
    kw = dict(n_cores=3, design="RASA-WLBP", bw_bytes_per_cycle=48.0,
              backend="fast")
    assert _same_outcome(
        run_batcher(reqs, ChipConfig(**kw), policy="degraded"),
        run_batcher(reqs, ChipConfig(**kw), policy="occupancy"))

    occ = run_batcher(reqs, ChipConfig(fault_plan=plan, **kw),
                      policy="occupancy")
    deg = run_batcher(reqs, ChipConfig(fault_plan=plan, **kw),
                      policy="degraded")
    admit_occ = dict(zip(occ.names, occ.admit_epochs))
    admit_deg = dict(zip(deg.names, deg.admit_epochs))
    # occupancy admits in arrival order: the heavy prefill first
    assert admit_occ["h0"] < admit_occ["l1"]
    # degraded sheds it until the core comes back; the lights jump ahead
    assert admit_deg["h0"] >= 200
    assert admit_deg["l1"] < admit_deg["h0"]
    assert admit_deg["l1"] <= admit_occ["l1"]
    # shedding is load-shaping, not load-shedding: everything still served
    assert deg.macs == occ.macs
    assert not any(math.isinf(f) for f in deg.finish_times)


def test_phase_aware_beats_occupancy_on_decode_heavy_model_trace():
    """The satellite pin: on a decode-heavy real-model trace (short
    prompts, long decode chains) behind a burst of prefill-heavy
    requests, capping concurrent prefills must cut the decode class's
    mean latency (and the p50) below plain occupancy."""
    from repro.serving.simbatch import model_trace
    from repro.workload.compile import CompileOptions
    opt = CompileOptions(dim_cap=128, max_layers=1)
    heavy = model_trace("qwen3-1.7b", 4, seed=0, mean_gap=0,
                        prompt_lens=(256,), decode_steps=(1,),
                        decode_batch=8, options=opt)
    light = model_trace("qwen3-1.7b", 8, seed=1, mean_gap=1,
                        prompt_lens=(16,), decode_steps=(8,),
                        decode_batch=8, options=opt)
    reqs = tuple(dataclasses.replace(r, name=f"h{i}")
                 for i, r in enumerate(heavy)) + \
        tuple(dataclasses.replace(r, name=f"l{i}")
              for i, r in enumerate(light))
    assert all(r.prefill_heavy for r in reqs[:4])
    assert not any(r.prefill_heavy for r in reqs[4:])
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=64.0, backend="fast")
    occ = run_batcher(reqs, chip, policy="occupancy")
    pha = run_batcher(reqs, chip, policy="phase_aware")

    def decode_mean(rep):
        lat = [l for n, l in zip(rep.names, rep.latencies)
               if n.startswith("l")]
        return sum(lat) / len(lat)

    # a real win, not a tie-breaker: the decode class's mean latency
    # drops by at least 10% once the prefill storm is capped
    assert decode_mean(pha) < 0.9 * decode_mean(occ)
    assert pha.macs == occ.macs


def test_phase_aware_cap_inert_on_pure_decode_trace():
    """With no prefill-heavy request in flight the cap never binds:
    phase_aware degenerates to occupancy exactly."""
    reqs = tuple(_light(f"l{i}", i) for i in range(5))
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0, backend="fast")
    assert _same_outcome(run_batcher(reqs, chip, policy="phase_aware"),
                         run_batcher(reqs, chip, policy="occupancy"))
