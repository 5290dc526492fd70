"""Unified span-arbiter tests: the single fixed-point implementation
(`repro.multicore.arbiter`) serving both the closed-batch cluster and the
open-arrival chip -- closed-vs-online bit-equivalence, share-policy
conservation, demand-weighted shares beating equal shares, heterogeneous
BASE/RASA core mixes end-to-end on every backend, prefix caching and
retired-span pruning."""

import dataclasses
import functools
from collections import defaultdict

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import GemmSpec, TABLE_I, simulate
from repro.core.timing import PipelineSimulator
from repro.multicore import (ChipConfig, CoreSpec, DemandWeightedShare,
                             EpochBandwidthLoadModel, OnlineChip,
                             SharePolicy, Span, SpanArbiter,
                             build_share_schedule, get_share_policy,
                             simulate_chip)
from repro.multicore.chip import CoreCluster, _lower_many
from repro.multicore.scheduler import assign

REL = 1e-6
SMALL = GemmSpec("small", 128, 256, 256)
BIG = GemmSpec("big", 256, 768, 768)

#: backends every end-to-end scenario must agree on
BACKENDS = ["reference", "numpy", "jax"]


def _skewed_workload():
    return [TABLE_I["DLRM-2"], SMALL, SMALL, SMALL, SMALL, SMALL]


#: the canonical balanced heterogeneous workload: the BASE core runs one
#: copy of the GEMM, the ~6x faster RASA-DMDB-WLS core runs six -- equal
#: unthrottled durations, very different bytes/cycle demands.
HET_WL = [BIG] + [dataclasses.replace(BIG, name=f"b{i}") for i in range(6)]
MIXED2 = ("BASE", "RASA-DMDB-WLS")


# ---------------------------------------------------------------- policies
def test_share_policy_registry():
    assert isinstance(get_share_policy("equal"), SharePolicy)
    assert isinstance(get_share_policy("demand"), DemandWeightedShare)
    p = DemandWeightedShare(floor=0.5)
    assert get_share_policy(p) is p
    with pytest.raises(ValueError):
        get_share_policy("fair")
    assert get_share_policy("equal").weight(123.0) == 1.0
    assert get_share_policy("demand").weight(12.5) == 12.5
    assert get_share_policy("demand").weight(0.0) > 0.0   # floor


@given(spans=st.lists(st.tuples(st.integers(0, 12), st.integers(1, 12),
                                st.floats(min_value=1e-3, max_value=100.0)),
                      min_size=1, max_size=12),
       budget=st.floats(min_value=1.0, max_value=1024.0))
@settings(max_examples=60, deadline=None)
def test_weighted_share_conservation_property(spans, budget):
    """Policy-independent conservation: per epoch, the active spans'
    weighted shares sum to exactly the budget (and never exceed it) --
    grants can then never outrun the budget beyond the bucket slack."""
    sp = [Span(start=s, end=s + d, demands=True, weight=w)
          for s, d, w in spans]
    arb = SpanArbiter(budget, 256.0, "demand")
    arb._rebuild(sp, 0)
    shares = arb.share_trace
    for e in range(len(shares)):
        active = [x for x in sp if x.start <= e < x.end]
        total = sum(shares[e] * x.weight for x in active)
        assert total <= budget * (1 + 1e-9)
        if active:
            assert total == pytest.approx(budget)


def test_equal_weight_schedule_matches_build_share_schedule():
    """With unit weights the engine's schedule is exactly the standalone
    equal-share builder's, bit for bit."""
    spans = [(0, 4), (0, None), (2, 9), (3, 3), (5, 7)]
    shares, n_active = build_share_schedule(spans, 24.0)
    sp = [Span(start=s, end=e, demands=True) for s, e in spans]
    arb = SpanArbiter(24.0, 256.0, "equal")
    arb._rebuild(sp, 0)
    assert list(arb.share_trace) == shares
    assert list(arb.active_trace) == n_active


def test_rebuild_pads_idle_gap():
    """A relaxation whose dirty epoch lies beyond the settled horizon must
    zero-fill the idle gap, not misalign the schedule."""
    arb = SpanArbiter(16.0, 256.0)
    arb._rebuild([Span(start=0, end=2, demands=True)], 0)
    assert arb.active_trace == (1, 1)
    # chip idle during epochs 2..5, new span at 5
    arb._rebuild([Span(start=5, end=7, demands=True)], 5)
    assert arb.active_trace == (1, 1, 0, 0, 0, 1, 1)
    assert arb.share_trace[3] == 0.0       # idle epoch: nothing flows


def test_idle_epoch_share_is_zero():
    """Fully-idle epochs report 0.0 shared bandwidth, not the full budget.

    Pre-fix, ``share_trace`` rendered ``budget`` for epochs with
    ``_wsum[e] == 0`` (in both the plain and ``budget_factors`` branches),
    painting idle gaps as fully-shared in ``ChipReport.share_trace`` and
    the Perfetto counter tracks."""
    spans = [Span(start=0, end=2, demands=True),
             Span(start=4, end=6, demands=True)]
    arb = SpanArbiter(16.0, 256.0)
    arb._rebuild(spans, 0)
    assert arb.share_trace == (16.0, 16.0, 0.0, 0.0, 16.0, 16.0)
    # derated variant: busy epochs scale with the factor, idle stays 0.0
    arb = SpanArbiter(16.0, 256.0, budget_factors=(1.0, 0.5, 0.5, 0.5))
    arb._rebuild(spans, 0)
    assert arb.share_trace == (16.0, 8.0, 0.0, 0.0, 16.0, 16.0)


# ------------------------------------------- single-implementation guard
def test_both_clients_delegate_to_span_arbiter(monkeypatch):
    """The relaxation exists once: both the closed-batch cluster and the
    online chip must route through SpanArbiter.relax."""
    calls = []
    orig = SpanArbiter.relax

    def spy(self, spans, simulate, dirty_from=0, **kwargs):
        calls.append(len(spans))
        return orig(self, spans, simulate, dirty_from, **kwargs)

    monkeypatch.setattr(SpanArbiter, "relax", spy)
    simulate_chip(_skewed_workload(),
                  ChipConfig(n_cores=2, design="RASA-WLBP",
                             bw_bytes_per_cycle=24.0),
                  scheduler="work_queue")
    assert calls, "closed-batch cluster did not delegate to SpanArbiter"
    closed_calls = len(calls)
    oc = OnlineChip(ChipConfig(n_cores=2, design="RASA-WLBP",
                               bw_bytes_per_cycle=24.0))
    oc.submit(0, [SMALL])
    oc.drain()
    assert len(calls) > closed_calls, \
        "online chip did not delegate to SpanArbiter"


# ------------------------------------------- closed-vs-online equivalence
@pytest.mark.parametrize("backend", BACKENDS + ["fast"])
def test_online_all_at_epoch0_reproduces_closed_batch(backend):
    """Submitting every core's shard as one segment at epoch 0 makes the
    open-arrival model the closed batch: per-core cycles, makespan and the
    converged share/active traces must reproduce the closed-batch
    ChipReport bit-exactly on the same backend."""
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=24.0, backend=backend)
    shards = assign(_skewed_workload(), chip, "lpt")
    rep = simulate_chip(_skewed_workload(), chip, scheduler="lpt")

    oc = OnlineChip(chip)
    segs = {c: oc.submit(c, shard) for c, shard in enumerate(shards)
            if shard}
    oc.drain()
    exact = backend != "jax"    # the jax closed path reorders float ops;
    # the online model always runs the numpy segment runner

    def check(a, b):
        if exact:
            assert a == b
        else:
            assert a == pytest.approx(b, rel=REL)

    check(oc.makespan, rep.cycles)
    for c, seg in segs.items():
        check(oc.finish_time(seg), rep.per_core_cycles[c])
        assert seg.start == 0
    assert oc.active_trace == rep.active_trace
    for a, b in zip(oc.share_trace, rep.share_trace):
        check(a, b)


def test_online_epoch0_equivalence_under_demand_policy():
    """The closed-vs-online equivalence holds for the demand-weighted
    policy too: same weights, same weighted schedule, same results."""
    chip = ChipConfig(cores=MIXED2, bw_bytes_per_cycle=48.0,
                      share_policy="demand")
    shards = assign(HET_WL, chip, "lpt")
    rep = simulate_chip(HET_WL, chip, scheduler="lpt")
    oc = OnlineChip(chip)
    segs = {c: oc.submit(c, shard) for c, shard in enumerate(shards)
            if shard}
    oc.drain()
    assert oc.makespan == rep.cycles
    for c, seg in segs.items():
        assert oc.finish_time(seg) == rep.per_core_cycles[c]
        assert seg.weight == pytest.approx(rep.core_weights[c])
    assert oc.active_trace == rep.active_trace


# --------------------------------------------------- demand-weighted shares
def test_demand_weighted_beats_equal_on_skewed_demand():
    """The balanced heterogeneous workload: durations match but the RASA
    core demands ~6x the bytes/cycle of the BASE core.  Equal shares
    throttle the hungry core while the other's unused allowance evaporates
    in the bucket; demand weighting splits the budget in proportion and
    strictly improves the makespan."""
    mk = lambda pol: simulate_chip(
        HET_WL, ChipConfig(cores=MIXED2, bw_bytes_per_cycle=64.0,
                           share_policy=pol), scheduler="lpt")
    eq, dm = mk("equal"), mk("demand")
    assert dm.cycles < eq.cycles * 0.9      # >10% better (measured ~20%)
    assert dm.share_policy == "demand" and eq.share_policy == "equal"
    assert eq.core_weights == (1.0, 1.0)
    w_base, w_rasa = dm.core_weights
    assert w_rasa > 3 * w_base              # the demand skew it measured
    assert dm.macs == eq.macs


def test_demand_weighted_cluster_conservation_on_real_streams():
    """Replaying the converged *weighted* schedule with grant recording:
    aggregate bytes per epoch stay within the chip budget (plus per-core
    burst carryover and straddling-tile slack) -- the conservation
    property is policy-independent."""
    chip = ChipConfig(cores=MIXED2, bw_bytes_per_cycle=48.0,
                      bw_burst_bytes=2048.0, share_policy="demand")
    shards = assign(HET_WL, chip, "lpt")
    streams = [_lower_many(shard, chip.cores[c].policy)
               for c, shard in enumerate(shards)]
    cluster = CoreCluster(chip)
    _, _, trace = cluster.run_streams(streams)
    weights = cluster.core_weights
    per_epoch: dict[int, float] = defaultdict(float)
    max_tile = 0
    for c, stream in enumerate(streams):
        cfg = chip.cores[c].engine
        model = EpochBandwidthLoadModel(
            cfg.load_ports, [s * weights[c] for s in trace.shares],
            trace.epoch_cycles, tail_share=chip.bw_bytes_per_cycle,
            burst_bytes=chip.bw_burst_bytes,
            store_ports=chip.store_ports_for(c),
            charge_store_bytes=True, record_grants=True)
        PipelineSimulator(cfg, load_model=model).run(stream)
        for start, n_bytes in model.grants:
            per_epoch[int(start // trace.epoch_cycles)] += n_bytes
            max_tile = max(max_tile, n_bytes)
    E = trace.epoch_cycles
    budget = chip.bw_bytes_per_cycle
    slack = chip.n_cores * (chip.bw_burst_bytes + 2 * max_tile)
    for e, granted in per_epoch.items():
        assert granted <= budget * E + slack + 1e-6, f"epoch {e}"


def test_demand_policy_static_arbitration_stays_equal():
    """arbitration='static' is the frozen equal-share baseline; the share
    policy only drives the epoch arbiter."""
    rep = simulate_chip(HET_WL,
                        ChipConfig(cores=MIXED2, bw_bytes_per_cycle=48.0,
                                   arbitration="static",
                                   share_policy="demand"),
                        scheduler="lpt")
    assert rep.core_weights == (1.0, 1.0)
    assert rep.share_policy == "equal"     # the report says so, too


# ------------------------------------------------ heterogeneous core mixes
def test_chipconfig_core_vector_validation():
    chip = ChipConfig(cores=MIXED2)
    assert chip.n_cores == 2
    assert chip.cores == (CoreSpec("BASE"), CoreSpec("RASA-DMDB-WLS"))
    assert not chip.homogeneous
    assert chip.design_name == "mixed[BASE+RASA-DMDB-WLS]"
    with pytest.raises(ValueError):
        chip.engine                      # no single engine on a mixed chip
    assert chip.core_engine(0).name == "BASE"
    # homogeneous chips keep the single-engine shorthand
    homo = ChipConfig(n_cores=3, design="RASA-WLBP")
    assert homo.homogeneous and homo.engine.name == "RASA-WLBP"
    assert homo.core_specs == (CoreSpec("RASA-WLBP"),) * 3
    with pytest.raises(ValueError):
        ChipConfig(n_cores=3, cores=MIXED2)          # inconsistent
    with pytest.raises(ValueError):
        ChipConfig(cores=())
    with pytest.raises(KeyError):
        ChipConfig(cores=("RASA-TURBO",))            # unknown design
    # single_core picks the requested spec and stays consistent
    one = chip.single_core(1)
    assert one.n_cores == 1 and one.cores == (CoreSpec("RASA-DMDB-WLS"),)


def test_chipconfig_replace_rederives_default_cores():
    """The documented frozen-dataclass idiom keeps working: replacing
    design or n_cores on a default (replicated) chip re-derives the core
    vector; an explicit ``cores`` tuple stays authoritative."""
    base = ChipConfig(n_cores=4)
    rebased = dataclasses.replace(base, design="BASE")
    assert rebased.core_specs == (CoreSpec("BASE"),) * 4
    assert rebased.engine.name == "BASE"
    grown = dataclasses.replace(base, n_cores=8)
    assert grown.n_cores == 8 and len(grown.core_specs) == 8
    # explicit cores: design changes don't silently clobber the mix...
    mixed = ChipConfig(cores=MIXED2)
    redesigned = dataclasses.replace(mixed, design="BASE")
    assert redesigned.core_specs == mixed.core_specs
    # ...and resizing a heterogeneous chip must be explicit
    with pytest.raises(ValueError):
        dataclasses.replace(mixed, n_cores=4)


@functools.lru_cache(maxsize=None)
def _mixed4_report(backend):
    return simulate_chip(
        HET_WL, ChipConfig(cores=("BASE", "BASE", "RASA-WLBP",
                                  "RASA-WLBP"),
                           bw_bytes_per_cycle=48.0, backend=backend),
        scheduler="lpt")


@pytest.mark.parametrize("backend", BACKENDS)
def test_mixed_chip_end_to_end_backend_parity(backend):
    """A mixed BASE/RASA chip runs partition -> schedule -> arbitrate ->
    report on every backend, and the backends agree."""
    chip = lambda be: ChipConfig(cores=("BASE", "BASE", "RASA-WLBP",
                                        "RASA-WLBP"),
                                 bw_bytes_per_cycle=48.0, backend=be)
    ref = _mixed4_report("reference")
    rep = _mixed4_report(backend)
    assert rep.cycles == pytest.approx(ref.cycles, rel=REL)
    assert rep.per_core_cycles == pytest.approx(ref.per_core_cycles,
                                                rel=REL)
    assert rep.bw_stall_cycles == pytest.approx(ref.bw_stall_cycles,
                                                rel=REL, abs=1e-6)
    assert rep.n_mm == ref.n_mm and rep.wl_skips == ref.wl_skips
    assert rep.active_trace == ref.active_trace
    assert rep.core_designs == ("BASE", "BASE", "RASA-WLBP", "RASA-WLBP")
    # the partitioned (single-GEMM) entry point flows through too
    part = simulate_chip(BIG, chip(backend), partition="m_split")
    assert part.cycles > 0 and part.macs == BIG.macs


def test_mixed_chip_partitioned_gemm_all_backends():
    """One GEMM sharded across a mixed chip: every backend agrees and the
    slow cores' shards dominate the makespan."""
    mk = lambda be: simulate_chip(
        BIG, ChipConfig(cores=("BASE", "RASA-DMDB-WLS"),
                        bw_bytes_per_cycle=64.0, backend=be),
        partition="m_split")
    ref = mk("reference")
    for be in [b for b in BACKENDS if b != "reference"]:
        rep = mk(be)
        assert rep.cycles == pytest.approx(ref.cycles, rel=REL), be
        assert rep.per_core_cycles == pytest.approx(ref.per_core_cycles,
                                                    rel=REL), be


def test_het_scheduler_routes_reuse_friendly_to_rasa():
    """On a mixed chip the LPT scheduler must place the dominant
    (WLBP-favoring) GEMMs on the RASA cores that finish them first, and
    the mixed chip must beat the all-BASE chip end to end."""
    chip = ChipConfig(cores=("BASE", "RASA-DMDB-WLS"),
                      bw_bytes_per_cycle=256.0)
    shards = assign(HET_WL, chip, "lpt")
    # the fast core must take the lion's share of the balanced workload
    assert len(shards[1]) > len(shards[0])
    assert len(shards[0]) >= 1              # ...but BASE is not idle
    mixed = simulate_chip(HET_WL, chip, scheduler="lpt")
    allbase = simulate_chip(
        HET_WL, ChipConfig(cores=("BASE", "BASE"),
                           bw_bytes_per_cycle=256.0), scheduler="lpt")
    assert mixed.cycles < allbase.cycles
    assert mixed.macs == allbase.macs


def test_het_scheduler_n1_reduction():
    """A one-core 'mix' reduces exactly to the single-core simulator
    through the scheduler entry point (cf. the homogeneous reduction)."""
    chip = ChipConfig(cores=("RASA-WLBP",))
    wl = [SMALL, TABLE_I["DLRM-2"], SMALL]
    cfg = chip.core_engine(0)
    ref = PipelineSimulator(cfg).run(_lower_many(wl, chip.cores[0].policy))
    for sched in ("work_queue", "lpt", "gang"):
        rep = simulate_chip(wl, chip, scheduler=sched)
        assert rep.cycles == ref.cycles, sched
        assert rep.bw_stall_cycles == 0.0, sched


def test_homogeneous_placements_unchanged_by_per_core_estimates():
    """On a homogeneous chip the per-(GEMM, core) estimates are constant
    across cores, so every scheduler's placement must equal the classic
    free-at rule's -- pinned against a golden placement."""
    chip = ChipConfig(n_cores=3, design="RASA-WLBP")
    wl = _skewed_workload()
    shards = assign(wl, chip, "lpt")
    # LPT: DLRM-2 dominates on core 0, smalls round out the other cores
    names = [tuple(s.name for s in core) for core in shards]
    assert names[0][0] == "DLRM-2"
    assert sorted(n for core in names for n in core) == \
        sorted(s.name for s in wl)


def test_online_mixed_chip_per_core_engines():
    """Online segments run on their core's own engine: the same GEMM
    finishes far faster on the RASA core of a mixed chip."""
    chip = ChipConfig(cores=("BASE", "RASA-DMDB-WLS"),
                      bw_bytes_per_cycle=256.0)
    oc = OnlineChip(chip)
    a = oc.submit(0, [SMALL])
    b = oc.submit(1, [SMALL])
    oc.drain()
    assert oc.finish_time(a) > 2 * oc.finish_time(b)
    ref = simulate(SMALL, "RASA-DMDB-WLS")
    assert oc.finish_time(b) == pytest.approx(ref.cycles, rel=REL)


# ---------------------------------------------- prefix cache and pruning
def _mid_trace_run(prefix_cache):
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0)
    oc = OnlineChip(chip, snap_stride=512, prefix_cache=prefix_cache)
    segs = []
    for k in range(8):
        segs.append(oc.submit(k % 2, [SMALL]))
        oc.advance_to(oc.epoch + 3)
    oc.drain()
    return oc, segs


def test_prefix_cache_identical_results_and_prunes():
    """The settled-prefix cache and retired-span pruning change the work,
    never the answer: identical finish times and traces, with retirement
    actually happening on the cached path."""
    on, segs_on = _mid_trace_run(True)
    off, segs_off = _mid_trace_run(False)
    assert on.makespan == off.makespan
    for a, b in zip(segs_on, segs_off):
        assert on.finish_time(a) == off.finish_time(b)
        assert (a.start, a.end) == (b.start, b.end)
    assert on.share_trace == off.share_trace
    assert on.active_trace == off.active_trace
    assert on.n_retired > 0                 # pruning happened...
    assert off.n_retired == 0               # ...only on the cached path


def test_prefix_cache_batcher_report_identity():
    """run_batcher(prefix_cache=False) is the rebuild-from-epoch-0
    baseline: bit-identical BatchReport, linearly more arbiter work."""
    from repro.serving.simbatch import run_batcher, synthetic_trace
    reqs = synthetic_trace(10, seed=3, mean_gap=2, d_model=256,
                           prompt_lens=(32, 64), decode_steps=(1, 2))
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=48.0)
    on = run_batcher(reqs, chip, policy="occupancy", prefix_cache=True)
    off = run_batcher(reqs, chip, policy="occupancy", prefix_cache=False)
    assert on == off


# ------------------------------------------------------- relaxation guards
def test_span_arbiter_validation():
    with pytest.raises(ValueError):
        SpanArbiter(0.0, 1024.0)
    with pytest.raises(ValueError):
        SpanArbiter(16.0, 0.0)
    arb = SpanArbiter(16.0, 1024.0)
    trace = arb.relax([], lambda jobs: None)
    assert trace.rounds == 1 and trace.shares == ()


def test_relax_skips_are_validated_against_oracle():
    """The skip rules must not change the fixed point: reference (oracle,
    skip-free) and fast (skipping) agree, and the oracle records zero
    skips while the fast path records some."""
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0)
    wl = [TABLE_I["DLRM-2"], TABLE_I["BERT-1"], TABLE_I["DLRM-2"],
          TABLE_I["BERT-1"], TABLE_I["DLRM-2"], TABLE_I["DLRM-2"]]
    fast = simulate_chip(wl, chip, scheduler="lpt")
    ref = simulate_chip(wl, dataclasses.replace(chip, backend="reference"),
                        scheduler="lpt")
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL)
    assert ref.arb_skipped == (0,) * ref.arb_rounds
    assert sum(fast.arb_skipped) > 0
