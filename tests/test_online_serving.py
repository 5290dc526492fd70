"""Contention-aware serving batcher tests: open-arrival semantics, cross-
backend parity of every arrival/departure scenario, admission-policy
behavior (occupancy-aware must beat fixed-batch on the skewed 4-core
trace), degenerate inputs, and the hypothesis property that no request is
lost, duplicated, or completed before it arrives."""

import dataclasses
import math

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import GemmSpec, simulate
from repro.multicore import ChipConfig, OnlineChip
from repro.serving.simbatch import (POLICIES, run_batcher, skewed_trace,
                                    synthetic_trace)

REL = 1e-6
SMALL = GemmSpec("small", 128, 256, 256)


def _mini_skew():
    """Scaled-down canonical skewed trace (oracle-affordable)."""
    return skewed_trace(d_model=256, heavy_prompt=256, n_light=6)


#: named arrival/departure scenarios of the parity suite: (requests, chip
#: kwargs).  Small enough that the reference oracle stays affordable.
SCENARIOS = {
    "steady": (synthetic_trace(5, seed=1, mean_gap=2, d_model=256,
                               prompt_lens=(32, 64), decode_steps=(1, 2)),
               dict(n_cores=2, design="RASA-WLBP",
                    bw_bytes_per_cycle=32.0)),
    "burst": (synthetic_trace(6, seed=2, mean_gap=0, d_model=256,
                              prompt_lens=(32,), decode_steps=(1,)),
              dict(n_cores=3, design="RASA-DMDB-WLS",
                   bw_bytes_per_cycle=48.0)),
    "skewed4": (_mini_skew(),
                dict(n_cores=4, design="RASA-WLBP",
                     bw_bytes_per_cycle=64.0)),
}


# --------------------------------------------------- cross-backend parity
@pytest.mark.parametrize("policy", ["fixed", "occupancy"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_batcher_backend_parity(scenario, policy):
    """Identical makespans (and per-request finishes) on the reference,
    fast and numpy backends for every scenario in the parity suite."""
    requests, kwargs = SCENARIOS[scenario]
    reps = {be: run_batcher(requests,
                            ChipConfig(backend=be, **kwargs),
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


# ----------------------------------------------------- policy behavior
def test_occupancy_beats_fixed_on_skewed_trace():
    """The acceptance scenario: on the skewed 4-core trace the
    occupancy-aware policy achieves strictly lower makespan than the
    fixed-batch baseline at equal offered load."""
    requests, kwargs = SCENARIOS["skewed4"]
    fixed = run_batcher(requests, ChipConfig(**kwargs), policy="fixed")
    occ = run_batcher(requests, ChipConfig(**kwargs), policy="occupancy")
    assert occ.makespan < fixed.makespan
    assert occ.p50_latency <= fixed.p50_latency
    assert occ.macs == fixed.macs      # same offered load either way


def test_predicted_no_worse_than_occupancy_on_skewed_trace():
    """The predicted-occupancy policy forecasts departures from the
    settled share-schedule prefix instead of reacting to current
    occupancy: on the skewed 4-core trace it must be no worse than
    ``occupancy`` (and, like it, strictly beat the fixed baseline)."""
    requests, kwargs = SCENARIOS["skewed4"]
    occ = run_batcher(requests, ChipConfig(**kwargs), policy="occupancy")
    pred = run_batcher(requests, ChipConfig(**kwargs), policy="predicted")
    fixed = run_batcher(requests, ChipConfig(**kwargs), policy="fixed")
    assert pred.makespan <= occ.makespan
    assert pred.makespan < fixed.makespan
    assert pred.macs == occ.macs
    # full-scale skew as well (the benchmark's acceptance scenario)
    full = skewed_trace()
    occ_f = run_batcher(full, ChipConfig(**kwargs), policy="occupancy")
    pred_f = run_batcher(full, ChipConfig(**kwargs), policy="predicted")
    assert pred_f.makespan <= occ_f.makespan


def test_predicted_backend_parity():
    """The predicted policy's admission decisions and timings agree across
    the reference, fast and numpy backends."""
    requests, kwargs = SCENARIOS["steady"]
    reps = {be: run_batcher(requests, ChipConfig(backend=be, **kwargs),
                            policy="predicted", snap_stride=512)
            for be in ("reference", "fast", "numpy")}
    ref = reps["reference"]
    for be in ("fast", "numpy"):
        assert reps[be].makespan == pytest.approx(ref.makespan, rel=REL)
        assert reps[be].finish_times == pytest.approx(ref.finish_times,
                                                      rel=REL)
        assert reps[be].admit_epochs == ref.admit_epochs


def test_predicted_queues_on_soon_free_core():
    """With a positive lookahead the predicted policy may queue behind a
    core that drains within the window -- admissions can land strictly
    earlier than occupancy's, never later; lookahead=0 degenerates to
    reacting to settled-idle cores only."""
    requests = synthetic_trace(6, seed=7, mean_gap=1, d_model=256,
                               prompt_lens=(64,), decode_steps=(2,))
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=48.0)
    occ = run_batcher(requests, chip, policy="occupancy")
    pred = run_batcher(requests, chip, policy="predicted", lookahead=4)
    assert all(p <= o for p, o in zip(pred.admit_epochs,
                                      occ.admit_epochs))
    zero = run_batcher(requests, chip, policy="predicted", lookahead=0)
    assert zero.n_requests == len(requests)
    with pytest.raises(ValueError):
        run_batcher(requests, chip, policy="predicted", lookahead=-1)


def test_bandwidth_threshold_paces_admission():
    """A high share floor forces serial admission; dropping it to zero
    admits everything at arrival."""
    requests = synthetic_trace(4, seed=3, mean_gap=0, d_model=256,
                               prompt_lens=(32,), decode_steps=(1,))
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0)
    eager = run_batcher(requests, chip, policy="bandwidth", min_share=0.0)
    paced = run_batcher(requests, chip, policy="bandwidth",
                        min_share=1e9)
    assert eager.admit_epochs == (0, 0, 0, 0)
    # work conservation admits exactly one at a time: strictly staggered
    assert len(set(paced.admit_epochs)) == len(paced.admit_epochs)
    assert paced.makespan > eager.makespan


def test_fixed_batch_waits_for_full_group():
    """The fixed policy admits in groups of batch_size: nothing enters the
    chip until a full group (or the end of the trace) is waiting."""
    requests = synthetic_trace(5, seed=4, mean_gap=3, d_model=256,
                               prompt_lens=(32,), decode_steps=(1,))
    rep = run_batcher(requests, ChipConfig(n_cores=2, design="RASA-WLBP"),
                      policy="fixed", batch_size=2)
    arr = rep.arrival_epochs
    adm = rep.admit_epochs
    # each pair admitted together, when its second member has arrived
    assert adm[0] == adm[1] == max(arr[0], arr[1])
    assert adm[2] == adm[3] == max(arr[2], arr[3])
    # the odd tail request enters once arrivals are exhausted
    assert adm[4] >= arr[4]
    # a larger group must keep the chip idle until it fills: the idle-chip
    # work-conservation override does not apply to the fixed baseline
    rep = run_batcher(requests, ChipConfig(n_cores=2, design="RASA-WLBP"),
                      policy="fixed", batch_size=4)
    adm = rep.admit_epochs
    assert adm[0] == adm[1] == adm[2] == adm[3] == max(arr[:4])
    assert adm[4] >= arr[4]


def test_report_preserves_submission_order():
    """Per-request arrays come back in the caller's order (with names),
    not arrival-sorted; makespan measures first arrival to last retire."""
    proto = synthetic_trace(3, seed=6, mean_gap=3, d_model=256,
                            prompt_lens=(32,), decode_steps=(1,))
    # distinct arrival epochs: with ties, FIFO (= submission) order would
    # legitimately change placement and thus the latencies themselves
    base = tuple(dataclasses.replace(r, arrival_epoch=4 * i)
                 for i, r in enumerate(proto))
    rev = tuple(reversed(base))
    chip = ChipConfig(n_cores=2, design="RASA-WLBP")
    fwd = run_batcher(base, chip, policy="occupancy")
    bwd = run_batcher(rev, chip, policy="occupancy")
    assert fwd.names == tuple(r.name for r in base)
    assert bwd.names == tuple(reversed(fwd.names))
    assert bwd.latencies == tuple(reversed(fwd.latencies))
    assert bwd.arrival_epochs == tuple(reversed(fwd.arrival_epochs))
    # a trace starting late is not charged the pre-arrival idle time
    late = [dataclasses.replace(r, arrival_epoch=r.arrival_epoch + 50)
            for r in base]
    shifted = run_batcher(late, chip, policy="occupancy")
    assert shifted.makespan == pytest.approx(fwd.makespan, rel=REL)


# -------------------------------------------------- degenerate inputs
def test_empty_trace():
    rep = run_batcher([], ChipConfig(n_cores=2))
    assert rep.makespan == 0.0
    assert rep.latencies == () and rep.n_requests == 0
    assert rep.p50_latency == 0.0 and rep.p99_latency == 0.0


def test_single_request_single_core_reduces_to_simulate():
    """One request on a one-core chip retires exactly when the plain
    single-engine simulation of its concatenated stream does."""
    requests = synthetic_trace(1, seed=0, d_model=256, prompt_lens=(64,),
                               decode_steps=(2,))
    chip = ChipConfig(n_cores=1, design="RASA-DMDB-WLS")
    rep = run_batcher(requests, chip, policy="occupancy")
    from repro.core.timing import PipelineSimulator
    from repro.multicore.chip import _lower_many
    ref = PipelineSimulator(chip.engine).run(
        _lower_many(requests[0].specs, chip.policy)).cycles
    assert rep.makespan == pytest.approx(ref, rel=REL)
    assert rep.latencies[0] == pytest.approx(ref, rel=REL)


def test_zero_headroom_still_completes():
    """min_share above the whole budget can never admit through the
    policy; work conservation must still drain the trace serially."""
    requests = synthetic_trace(3, seed=5, mean_gap=0, d_model=256,
                               prompt_lens=(32,), decode_steps=(1,))
    rep = run_batcher(requests, ChipConfig(n_cores=2, design="RASA-WLBP"),
                      policy="occupancy", min_share=math.inf)
    assert rep.n_requests == 3
    assert all(f > 0 for f in rep.finish_times)
    assert len(set(rep.admit_epochs)) == 3      # one at a time


def test_batcher_input_validation():
    with pytest.raises(ValueError):
        run_batcher([], ChipConfig(), policy="greedy")
    with pytest.raises(ValueError):
        run_batcher([], ChipConfig(), batch_size=0)
    reqs = synthetic_trace(2, seed=0)
    dup = (reqs[0], reqs[0])
    with pytest.raises(ValueError):
        run_batcher(dup, ChipConfig())
    with pytest.raises(TypeError):
        run_batcher([], ChipConfig(), n_cores=2)


# ------------------------------------------------- OnlineChip edge cases
def test_online_chip_validation():
    with pytest.raises(ValueError):
        OnlineChip(ChipConfig(arbitration="static"))
    with pytest.raises(ValueError):
        OnlineChip(ChipConfig(n_cores=2), snap_stride=0)
    oc = OnlineChip(ChipConfig(n_cores=2))
    with pytest.raises(ValueError):
        oc.submit(5, [SMALL])
    with pytest.raises(ValueError):
        oc.submit(0, [])
    oc.advance_to(3)
    with pytest.raises(ValueError):
        oc.advance_to(1)
    seg = oc.submit(0, [SMALL])
    assert seg.start == 3                      # starts at the current epoch
    queued = oc.submit(0, [SMALL])             # behind the first segment
    assert queued.start is None or queued.start > 3


def test_online_chip_departure_returns_bandwidth():
    """Arrivals raise n_active, departures lower it: the converged active
    trace steps up at the injection epoch and back down as work drains."""
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=24.0)
    oc = OnlineChip(chip)
    big = oc.submit(0, [GemmSpec("big", 512, 1024, 64)])
    oc.advance_to(2)
    small = oc.submit(1, [SMALL])
    oc.drain()
    active = oc.active_trace
    assert max(active) == 2
    # epochs before the arrival see only the first segment
    assert all(n == 1 for n in active[:2])
    # after the small one drains its share returns: tail is single-active
    assert active[-1] == 1
    assert oc.finish_time(big) > oc.finish_time(small)
    # and while both were active each epoch share was budget / n_active
    for share, n in zip(oc.share_trace, active):
        assert share == pytest.approx(24.0 / n)


def test_online_chip_live_queries():
    chip = ChipConfig(n_cores=2, design="RASA-WLBP")
    oc = OnlineChip(chip)
    assert oc.core_busy() == [False, False]
    assert oc.n_active() == 0
    assert oc.live_share() == chip.bw_bytes_per_cycle
    oc.submit(0, [SMALL])
    assert oc.core_busy() == [True, False]
    assert oc.n_active() == 1
    free = oc.free_at_estimate()
    assert free[0] > free[1] == 0.0
    queued = oc.submit(0, [SMALL])     # behind the running segment
    assert queued.start is None
    with pytest.raises(RuntimeError):
        oc.finish_time(queued)


# ------------------------------------------------ shared arrival process
def test_arrival_process_pinned_and_shared():
    """The RNG arrival loop exists once (``arrival_process``): its draw
    sequence is pinned so the synthetic/model trace dedup is provably
    behavior-preserving, and a seed yields the same arrival pattern in
    both builders."""
    from repro.serving.simbatch import arrival_process, model_trace
    menus = dict(prompt_lens=(32, 64, 128), decode_steps=(2, 4, 8))
    # generated by the pre-dedup synthetic_trace loop at seed=3, mean_gap=2
    assert arrival_process(8, 3, 2, **menus) == (
        (0, 0, 32, 8), (1, 4, 32, 4), (2, 8, 64, 8), (3, 12, 32, 8),
        (4, 12, 64, 4), (5, 16, 32, 2), (6, 19, 128, 8), (7, 22, 64, 8))
    syn = synthetic_trace(8, seed=3, mean_gap=2, d_model=64, **menus)
    assert tuple((r.arrival_epoch, r.specs[0].M, len(r.decode))
                 for r in syn) == (
        (0, 32, 8), (4, 32, 4), (8, 64, 8), (12, 32, 8), (12, 64, 4),
        (16, 32, 2), (19, 128, 8), (22, 64, 8))
    # the model-trace builder sees the identical arrival pattern
    mdl = model_trace("qwen3-1.7b", 8, seed=3, mean_gap=2,
                      prompt_lens=(16,), decode_steps=(2, 4, 8))
    assert tuple(r.arrival_epoch for r in mdl) == \
        tuple(e for _, e, _, _ in arrival_process(8, 3, 2, prompt_lens=(16,),
                                                  decode_steps=(2, 4, 8)))


# -------------------------------------------------- transactional settle
def test_settle_transactional_on_failing_simulate():
    """A settle whose simulate callback raises must leave the chip exactly
    as it was before the attempt -- arbiter prefix, stamps, per-segment
    results -- with the dirty marker intact, so the retried settle is
    bit-identical to a chip that never saw the failure.  Pre-fix, the
    partially rebuilt ``_wsum`` survived the exception and disagreed with
    the marker on retry."""
    requests, kwargs = SCENARIOS["steady"]
    chip = ChipConfig(backend="fast", **kwargs)

    def drive(sim):
        n = sim.chip.n_cores
        for i, r in enumerate(requests):
            if r.arrival_epoch > sim.epoch:
                sim.advance_to(r.arrival_epoch)
            sim.submit(i % n, r.specs)
        return sim

    clean = drive(OnlineChip(chip))
    clean.drain()

    sim = drive(OnlineChip(chip))
    arb = sim._arb
    pre_wsum, pre_nact = list(arb._wsum), list(arb._nact)
    pre_stamp = arb._stamp
    pre_segs = [(s.sid, s.result, s._snaps, s.span._vis, s.span.last_grant)
                for s in sim._active]

    def failing(seg, vis):
        raise RuntimeError("injected simulate failure")

    sim._simulate = failing
    with pytest.raises(RuntimeError, match="injected simulate failure"):
        sim.drain()            # queued segments start -> dirty -> settle

    # the failed attempt must not have torn any settle state
    assert list(arb._wsum) == pre_wsum
    assert list(arb._nact) == pre_nact
    assert arb._stamp == pre_stamp
    by_sid = {s.sid: s for s in sim._active}
    for sid, result, snaps, vis, lg in pre_segs:
        s = by_sid[sid]
        assert s.result is result and s._snaps is snaps
        assert s.span._vis == vis and s.span.last_grant == lg
    assert sim._dirty                      # marker survives the failure

    del sim._simulate                      # disarm: back to the real one
    sim.drain()                            # the retry settles cleanly
    assert sim.makespan == clean.makespan
    assert sim.share_trace == clean.share_trace
    assert sim.active_trace == clean.active_trace
    assert sim.n_retired == clean.n_retired


# --------------------------------------------------- hypothesis property
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 9), n=st.integers(1, 7),
       gap=st.integers(0, 4), policy=st.sampled_from(POLICIES),
       batch_size=st.integers(1, 4))
def test_no_request_lost_duplicated_or_early(seed, n, gap, policy,
                                             batch_size):
    """Open-arrival conservation: every submitted request is served exactly
    once, admitted no earlier than it arrived, and finishes strictly after
    both its arrival and its admission epoch."""
    requests = synthetic_trace(n, seed=seed, mean_gap=gap, d_model=128,
                               prompt_lens=(16, 32), decode_steps=(1, 2),
                               decode_batch=8)
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0, backend="numpy")
    rep = run_batcher(requests, chip, policy=policy,
                      batch_size=batch_size, snap_stride=256)
    assert rep.n_requests == n
    assert len(rep.latencies) == len(rep.finish_times) == n
    E = rep.epoch_cycles
    for req, admit, finish, lat in zip(requests, rep.admit_epochs,
                                       rep.finish_times, rep.latencies):
        assert admit >= req.arrival_epoch                  # not served early
        assert finish > admit * E                          # service > 0
        assert lat == pytest.approx(finish - req.arrival_epoch * E)
        assert lat > 0
    assert rep.makespan == max(rep.finish_times) - \
        min(rep.arrival_epochs) * E        # first arrival to last retire
    assert rep.macs == sum(r.macs for r in requests)       # nothing lost
