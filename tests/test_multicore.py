"""Multi-core chip model tests: partition coverage, single-core reduction,
scaling monotonicity, bandwidth contention (static + epoch-dynamic
arbitration, conservation), store-traffic accounting, and workload
scheduling including gang splits."""

import dataclasses
import math
from collections import defaultdict

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import DESIGNS, GemmSpec, TABLE_I, simulate
from repro.core.designs import get_design
from repro.core.engine import simulate_chip as core_simulate_chip
from repro.core.timing import LoadStreamModel, PipelineSimulator
from repro.multicore import (ChipConfig, EpochBandwidthLoadModel,
                             SharedBandwidthLoadModel, partition_gemm,
                             simulate_chip, split_ways)
from repro.multicore.chip import CoreCluster, _lower_many
from repro.multicore.partition import PARTITIONERS, _best_grid
from repro.multicore.scheduler import assign

SMALL = GemmSpec("small", 128, 256, 256)
ODD = GemmSpec("odd", 200, 96, 150)       # edge tiles in M and N
TILE_BYTES = 1024                         # largest single tile transfer


def _skewed_workload():
    return [TABLE_I["DLRM-2"], SMALL, SMALL, SMALL, SMALL, SMALL]


# ------------------------------------------------------------- partitioners
@pytest.mark.parametrize("strategy", PARTITIONERS)
@pytest.mark.parametrize("spec", [SMALL, ODD], ids=lambda s: s.name)
@pytest.mark.parametrize("n_cores", [1, 2, 3, 4, 8, 16])
def test_partition_conserves_macs(strategy, spec, n_cores):
    """Sharding conserves MACs: per-core MACs must sum to the GEMM's MACs
    (a K-split's ReduceSpec contributes zero -- a reduction multiplies
    nothing)."""
    shards = partition_gemm(spec, n_cores, strategy)
    assert len(shards) == n_cores
    total = sum(s.macs for shard in shards for s in shard)
    assert total == spec.macs
    gemms = [s for shard in shards for s in shard
             if isinstance(s, GemmSpec)]
    if strategy == "k_split":
        assert all(s.M == spec.M and s.N == spec.N for s in gemms)
        assert sum(s.K for s in gemms) == spec.K
    else:
        for s in gemms:
            assert s.K == spec.K        # output-space: K is never split


@pytest.mark.parametrize("n_cores", [2, 3, 4, 8])
def test_k_split_emits_one_reduction(n_cores):
    """A live K-split carries exactly one ReduceSpec, hosted by core 0,
    with one way per live K-chunk."""
    from repro.core.tiling import ReduceSpec
    shards = partition_gemm(SMALL, n_cores, "k_split")
    reduces = [s for shard in shards for s in shard
               if isinstance(s, ReduceSpec)]
    live = sum(1 for shard in shards
               if any(isinstance(s, GemmSpec) for s in shard))
    if live > 1:
        assert len(reduces) == 1
        assert reduces[0].ways == live
        assert reduces[0].M == SMALL.M and reduces[0].N == SMALL.N
        assert isinstance(shards[0][-1], ReduceSpec)
    else:
        assert not reduces


def test_k_split_n1_is_the_unsplit_gemm():
    """n_cores=1: one shard, same dims, no reduction."""
    [shard] = partition_gemm(SMALL, 1, "k_split")
    [only] = shard
    assert (only.M, only.K, only.N) == (SMALL.M, SMALL.K, SMALL.N)


def test_partition_more_cores_than_tiles():
    tiny = GemmSpec("tiny", 16, 32, 16)     # a single tile
    shards = partition_gemm(tiny, 8, "m_split")
    occupied = [s for s in shards if s]
    assert len(occupied) == 1
    assert occupied[0][0].M == 16


def test_split_ways_drops_empty_shards():
    assert split_ways(SMALL, 1, "m_split") == [SMALL]   # identity at w=1
    tiny = GemmSpec("tiny", 16, 32, 16)
    shards = split_ways(tiny, 8, "m_split")
    assert len(shards) == 1 and shards[0].M == 16
    four = split_ways(SMALL, 4, "m_split")
    assert len(four) == 4
    assert sum(s.macs for s in four) == SMALL.macs


def test_best_grid_prefers_square():
    assert _best_grid(16, 64, 64) == (4, 4)
    assert sorted(_best_grid(8, 64, 64)) == [2, 4]


def test_partition_rejects_unknown_strategy():
    with pytest.raises(ValueError):
        partition_gemm(SMALL, 4, "kn_split")


def test_split_ways_rejects_k_split():
    """Gangs place one shard per core; a K-split's reduction must ride its
    host shard, so split_ways refuses the strategy explicitly."""
    with pytest.raises(ValueError):
        split_ways(SMALL, 2, "k_split")


# ------------------------------------------------------ k_split cost model
def test_k_split_reduction_charges_shared_budget():
    """The reduction's partial traffic is real: tightening the chip budget
    must lengthen a K-split run (the merge bytes queue behind the same
    arbiter as tile loads), and a K-split is never reported cheaper than
    the work it does -- dynamic arbitration stays <= static throughout."""
    spec = GemmSpec("dec", 8, 4096, 512)        # decode shape: 1 tile row
    mk = lambda bw, arb: simulate_chip(
        spec, ChipConfig(n_cores=4, design="RASA-DMDB-WLS",
                         bw_bytes_per_cycle=bw, arbitration=arb),
        partition="k_split")
    loose = mk(math.inf, "epoch")
    tight = mk(32.0, "epoch")
    assert tight.cycles > loose.cycles
    assert tight.bw_stall_cycles > 0.0
    # the merge traffic flows through the span arbiter like any tile load:
    # the dynamic-share schedule must still dominate the frozen shares
    for bw in (32.0, 64.0, 256.0):
        assert mk(bw, "epoch").cycles <= mk(bw, "static").cycles, f"bw={bw}"


def test_k_split_scales_small_m_where_m_split_cannot():
    """The point of the partitioner: a decode GEMM with a single tile row
    cannot occupy more than one core under m_split, but K-split spreads it
    -- and still pays for its reduction (speedup strictly below linear)."""
    spec = GemmSpec("dec", 8, 4096, 512)
    chip = ChipConfig(n_cores=4, design="RASA-DMDB-WLS")
    m = simulate_chip(spec, chip, partition="m_split")
    k = simulate_chip(spec, chip, partition="k_split")
    assert sum(1 for c in m.per_core_cycles if c > 0) == 1
    assert m.speedup == pytest.approx(1.0)
    assert sum(1 for c in k.per_core_cycles if c > 0) == 4
    assert 1.0 < k.speedup < 4.0
    assert k.macs == m.macs == spec.macs


@pytest.mark.parametrize("backend", ["reference", "numpy", "jax"])
def test_k_split_backend_parity(backend):
    """Cross-backend parity on a K-split decode workload: the reduce
    stream (pure TL/TS, no rasa_mm) must time identically on the oracle
    loop and both fast backends."""
    if backend == "jax":
        pytest.importorskip("jax")
    spec = GemmSpec("dec", 8, 1024, 256)
    rep = simulate_chip(spec, ChipConfig(n_cores=4, design="RASA-WLBP",
                                         bw_bytes_per_cycle=64.0,
                                         backend=backend),
                        partition="k_split")
    ref = simulate_chip(spec, ChipConfig(n_cores=4, design="RASA-WLBP",
                                         bw_bytes_per_cycle=64.0,
                                         backend="reference"),
                        partition="k_split")
    assert rep.cycles == ref.cycles
    assert rep.per_core_cycles == ref.per_core_cycles
    assert rep.bw_stall_cycles == pytest.approx(ref.bw_stall_cycles)


# ----------------------------------------------- single-core exact reduction
@pytest.mark.parametrize("design", ["BASE", "RASA-WLBP", "RASA-DMDB-WLS"])
@pytest.mark.parametrize("strategy", PARTITIONERS)
def test_n1_reduces_to_single_core_simreport(design, strategy):
    """At n_cores=1 the chip model must reproduce the single-core simulator
    exactly: the default budget does not bind for one engine."""
    ref = simulate(SMALL, design)
    rep = simulate_chip(SMALL, ChipConfig(n_cores=1, design=design),
                        partition=strategy)
    assert rep.cycles == ref.cycles
    assert rep.speedup == 1.0 and rep.efficiency == 1.0
    assert rep.bw_stall_cycles == 0.0
    assert rep.utilization == pytest.approx(ref.utilization)


@pytest.mark.parametrize("arbitration", ["epoch", "static"])
@pytest.mark.parametrize("scheduler", ["work_queue", "gang"])
def test_n1_scheduler_reduces_to_single_core(scheduler, arbitration):
    """At n_cores=1 the scheduler entry point (submission order preserved by
    work_queue and gang) must reproduce the plain unthrottled single-core
    simulation of the concatenated workload, under both arbitrations."""
    wl = [SMALL, TABLE_I["DLRM-2"], SMALL]
    chip = ChipConfig(n_cores=1, design="RASA-WLBP", arbitration=arbitration)
    cfg = chip.engine
    ref = PipelineSimulator(cfg).run(_lower_many(wl, chip.policy)).cycles
    rep = simulate_chip(wl, chip, scheduler=scheduler)
    assert rep.cycles == ref
    assert rep.bw_stall_cycles == 0.0


def test_engine_reexport_delegates():
    a = core_simulate_chip(SMALL, ChipConfig(n_cores=2))
    b = simulate_chip(SMALL, ChipConfig(n_cores=2))
    assert a == b


# ------------------------------------------------------------------ scaling
@pytest.mark.parametrize("design", ["BASE", "RASA-DMDB-WLS"])
def test_speedup_monotone_under_infinite_bandwidth(design):
    """With no bandwidth cap, adding cores never slows the chip down."""
    chip = lambda n: ChipConfig(n_cores=n, design=design,
                                bw_bytes_per_cycle=math.inf)
    prev = -1.0
    for n in (1, 2, 4, 8, 16):
        rep = simulate_chip(SMALL, chip(n), partition="m_split")
        assert rep.speedup >= prev - 1e-9, f"n={n}"
        assert rep.efficiency <= 1.0 + 1e-9
        prev = rep.speedup


def test_bandwidth_binds_and_degrades_efficiency():
    """Once the shared budget binds, efficiency drops strictly below 1 and
    bandwidth-stall cycles appear; loosening the budget recovers speedup."""
    tight = simulate_chip(SMALL, ChipConfig(n_cores=8, design="RASA-DMDB-WLS",
                                            bw_bytes_per_cycle=64.0))
    loose = simulate_chip(SMALL, ChipConfig(n_cores=8, design="RASA-DMDB-WLS",
                                            bw_bytes_per_cycle=math.inf))
    assert tight.bw_stall_cycles > 0.0
    assert tight.efficiency < 1.0
    assert tight.cycles > loose.cycles
    assert 0.0 < tight.bw_stall_share < 1.0


def test_bw_stall_share_occupied_semantics():
    """bw_stall_share is defined against occupied core-cycles: makespan x
    cores that ran work -- not the sum of per-core runtimes, which would let
    drained-early cores shrink the denominator."""
    rep = simulate_chip(SMALL, ChipConfig(n_cores=8, design="RASA-DMDB-WLS",
                                          bw_bytes_per_cycle=64.0))
    active = sum(1 for c in rep.per_core_cycles if c > 0)
    assert rep.occupied_core_cycles == rep.cycles * active
    assert rep.bw_stall_share == pytest.approx(
        rep.bw_stall_cycles / (rep.cycles * active))
    # more cores than tile rows: idle cores must not enter the denominator
    tiny = GemmSpec("tiny2", 32, 64, 32)    # 2 tile rows
    rep = simulate_chip(tiny, ChipConfig(n_cores=8, design="BASE"),
                        partition="m_split")
    assert sum(1 for c in rep.per_core_cycles if c > 0) == 2
    assert rep.occupied_core_cycles == rep.cycles * 2


# ------------------------------------------------------- arbitration models
def test_shared_bandwidth_model_reduces_to_port_model():
    """share=inf must reproduce the plain load-port arbiter exactly."""
    model = SharedBandwidthLoadModel(2, math.inf)
    starts = [model.acquire(t, 1024) for t in (0.0, 0.0, 0.0, 10.0)]
    assert starts == [(0.0, 0.0), (0.5, 0.0), (1.0, 0.0), (10.0, 0.0)]


def test_throttle_delays_and_reports_stall():
    model = SharedBandwidthLoadModel(2, 1.0, burst_bytes=1024.0)
    t0, s0 = model.acquire(0.0, 1024)       # rides the burst allowance
    t1, s1 = model.acquire(0.0, 1024)       # must wait for bytes to drain
    assert (t0, s0) == (0.0, 0.0)
    assert t1 == pytest.approx(1024.0)
    assert s1 == pytest.approx(1024.0 - 0.5)


def test_token_bucket_caps_banked_allowance():
    """A core idle for a long time cannot bank unbounded credit: allowance
    accrual is capped at burst_bytes (a cumulative leaky-bucket line would
    grant ~98 banked tiles at t=100000 before throttling again)."""
    model = SharedBandwidthLoadModel(2, 1.0, burst_bytes=1024.0)
    model.acquire(0.0, 1024)                # drains the initial burst
    t1, _ = model.acquire(100_000.0, 1024)  # banked tokens capped at 1024
    t2, _ = model.acquire(100_000.0, 1024)  # bank exhausted: refill first
    assert t1 == pytest.approx(100_000.0)
    assert t2 == pytest.approx(100_000.0 + 1024.0)


def test_epoch_model_share_schedule_steps():
    """Shares step at epoch boundaries: a core alone from epoch 1 on is
    granted at the full budget there."""
    model = EpochBandwidthLoadModel(1, shares=(8.0,), epoch_cycles=100.0,
                                    tail_share=64.0, burst_bytes=400.0)
    t0, _ = model.acquire(0.0, 400)   # initial burst: granted immediately
    t1, _ = model.acquire(0.0, 400)   # 8 B/cyc: next 400 B ready at ~50
    t2, _ = model.acquire(0.0, 400)   # rest of epoch 0 refills exactly 400
    t3, _ = model.acquire(0.0, 400)   # epoch 1: tail share 64 B/cyc kicks in
    assert t0 == pytest.approx(0.0)
    assert t1 == pytest.approx(50.0)
    assert t2 == pytest.approx(100.0)
    assert t3 == pytest.approx(100.0 + 400.0 / 64.0, abs=0.2)


@given(shares=st.lists(st.floats(min_value=0.5, max_value=64.0),
                       min_size=1, max_size=8),
       gaps=st.lists(st.floats(min_value=0.0, max_value=32.0),
                     min_size=1, max_size=48),
       sizes=st.lists(st.integers(min_value=1, max_value=2048),
                      min_size=1, max_size=48),
       burst=st.floats(min_value=0.0, max_value=4096.0))
@settings(max_examples=30, deadline=None)
def test_epoch_conservation_property(shares, gaps, sizes, burst):
    """Token-bucket conservation: bytes granted within one epoch never
    exceed that epoch's budget share plus the bounded carryover (burst cap)
    plus the one grant that straddles the epoch edge."""
    E = 256.0
    model = EpochBandwidthLoadModel(2, shares, E, tail_share=8.0,
                                    burst_bytes=burst, record_grants=True)
    t = 0.0
    for gap, size in zip(gaps, sizes):
        t += gap
        model.acquire(t, size)
    per_epoch: dict[int, float] = defaultdict(float)
    for start, n_bytes in model.grants:
        per_epoch[int(start // E)] += n_bytes
    max_tile = max(sizes)
    for e, granted in per_epoch.items():
        share = shares[e] if e < len(shares) else 8.0
        assert granted <= share * E + burst + max_tile + 1e-6, \
            f"epoch {e}: granted {granted} over budget {share * E}"


def test_cluster_epoch_conservation_on_real_streams():
    """Chip-level conservation: replaying the converged schedule with grant
    recording, the cores' aggregate bytes per epoch stay within the chip
    budget (plus per-core burst carryover and straddling-tile slack)."""
    chip = ChipConfig(n_cores=2, design="RASA-WLBP", bw_bytes_per_cycle=24.0,
                      bw_burst_bytes=2048.0)
    cfg = chip.engine
    shards = assign(_skewed_workload(), chip, "work_queue")
    streams = [_lower_many(shard, chip.policy) for shard in shards]
    _, _, trace = CoreCluster(chip).run_streams(streams)
    assert trace is not None and trace.epoch_cycles == chip.epoch_cycles
    per_epoch: dict[int, float] = defaultdict(float)
    for stream in streams:
        model = EpochBandwidthLoadModel(
            cfg.load_ports, trace.shares, trace.epoch_cycles,
            tail_share=chip.bw_bytes_per_cycle,
            burst_bytes=chip.bw_burst_bytes, store_ports=chip.store_ports,
            charge_store_bytes=True, record_grants=True)
        PipelineSimulator(cfg, load_model=model).run(stream)
        for start, n_bytes in model.grants:
            per_epoch[int(start // trace.epoch_cycles)] += n_bytes
    E = trace.epoch_cycles
    budget = chip.bw_bytes_per_cycle
    # per-core slack: burst carryover + the straddling tile + one
    # retroactively-granted store (stores are served out of issue order)
    slack = chip.n_cores * (chip.bw_burst_bytes + 2 * TILE_BYTES)
    for e, granted in per_epoch.items():
        assert granted <= budget * E + slack + 1e-6, f"epoch {e}"


def test_dynamic_arbitration_beats_static_on_skew():
    """Early finishers return their share: on a skewed two-core workload a
    binding budget makes the epoch model's makespan strictly better than the
    frozen static-share model, and never worse anywhere."""
    wl = _skewed_workload()
    mk = lambda arb, bw: simulate_chip(
        wl, ChipConfig(n_cores=2, design="RASA-WLBP", bw_bytes_per_cycle=bw,
                       arbitration=arb), scheduler="work_queue")
    for bw in (24.0, 48.0, 96.0):
        dyn, sta = mk("epoch", bw), mk("static", bw)
        assert dyn.cycles <= sta.cycles, f"bw={bw}"
        assert dyn.n_mm == sta.n_mm
    dyn, sta = mk("epoch", 24.0), mk("static", 24.0)
    assert dyn.bw_stall_cycles > 0.0       # the budget binds...
    assert dyn.cycles < sta.cycles         # ...and dynamic strictly wins
    assert dyn.arbitration == "epoch" and sta.arbitration == "static"


def test_arbiter_trace_monotone_and_consistent():
    """The fixed point's activity trace is non-increasing (cores only ever
    drain) and shares are exactly budget / n_active per epoch."""
    rep = simulate_chip(_skewed_workload(),
                        ChipConfig(n_cores=2, design="RASA-WLBP",
                                   bw_bytes_per_cycle=24.0),
                        scheduler="work_queue")
    assert rep.epoch_cycles > 0 and len(rep.share_trace) > 0
    assert len(rep.share_trace) == len(rep.active_trace)
    for earlier, later in zip(rep.active_trace, rep.active_trace[1:]):
        assert earlier >= later
    for share, n in zip(rep.share_trace, rep.active_trace):
        assert share == pytest.approx(24.0 / n)
    assert rep.arb_rounds >= 2             # at least one horizon shrank


# ------------------------------------------------------- store accounting
def test_store_port_serializes_and_charges_bytes():
    model = SharedBandwidthLoadModel(2, 1.0, burst_bytes=1024.0,
                                     store_ports=1, charge_store_bytes=True)
    t0, s0 = model.acquire_store(0.0, 1024)   # rides the burst allowance
    t1, s1 = model.acquire_store(0.0, 1024)   # waits for tokens to refill
    assert (t0, s0) == (0.0, 0.0)
    assert t1 == pytest.approx(1024.0)
    assert s1 == pytest.approx(1024.0 - 1.0)  # port floor was 1.0


def test_loads_only_switch_recovers_free_stores():
    """store_ports=None (the base model and store_bytes_shared=False) keeps
    the paper's idealized stores: no serialization, no bytes."""
    base = LoadStreamModel(2)
    assert base.acquire_store(3.0, 1 << 20) == (3.0, 0.0)
    model = SharedBandwidthLoadModel(2, 1.0, burst_bytes=0.0)
    assert model.acquire_store(3.0, 1 << 20) == (3.0, 0.0)


def test_store_traffic_pressures_shared_budget():
    """Charging rasa_ts bytes against the chip budget can only lengthen a
    bandwidth-bound run; store_bytes_shared=False recovers the old
    loads-only makespan."""
    on = ChipConfig(n_cores=4, design="RASA-DMDB-WLS", bw_bytes_per_cycle=16.0)
    off = dataclasses.replace(on, store_bytes_shared=False)
    rep_on = simulate_chip(SMALL, on)
    rep_off = simulate_chip(SMALL, off)
    assert rep_on.cycles > rep_off.cycles
    assert rep_on.n_mm == rep_off.n_mm


# ---------------------------------------------------------------- scheduler
def test_work_queue_beats_round_robin_on_skew():
    """One big GEMM + many small ones on two cores: round-robin piles small
    GEMMs behind the big one, the dynamic queue routes them away."""
    chip = ChipConfig(n_cores=2, design="RASA-WLBP")
    wl = _skewed_workload()
    static = simulate_chip(wl, chip, scheduler="round_robin")
    dynamic = simulate_chip(wl, chip, scheduler="work_queue")
    assert dynamic.cycles < static.cycles
    assert static.n_mm == dynamic.n_mm      # same work either way


@pytest.mark.parametrize("scheduler", ["round_robin", "work_queue", "lpt"])
def test_schedulers_cover_all_gemms(scheduler):
    chip = ChipConfig(n_cores=3, design="BASE")
    wl = _skewed_workload()
    shards = assign(wl, chip, scheduler)
    names = sorted(s.name for shard in shards for s in shard)
    assert names == sorted(s.name for s in wl)


def test_gang_splits_dominant_gemm_and_beats_lpt():
    """A dominant GEMM that would leave cores idle under whole-GEMM LPT is
    gang-split across them; MACs are conserved through the split."""
    wl = [TABLE_I["DLRM-2"], SMALL, SMALL, SMALL, SMALL]
    chip = ChipConfig(n_cores=3, design="RASA-DMDB-WLS")
    lpt = simulate_chip(wl, chip, scheduler="lpt")
    gang = simulate_chip(wl, chip, scheduler="gang")
    assert gang.cycles < lpt.cycles
    assert gang.macs == lpt.macs == sum(s.macs for s in wl)
    # the dominant GEMM was actually split: its shards appear on >1 core
    gang_cores = sum(1 for core in gang.per_core_gemms
                     if any(n.startswith("DLRM-2") for n in core))
    assert gang_cores > 1


def test_gang_no_split_when_balanced():
    """On a balanced workload (one equal GEMM per core) splitting cannot
    finish earlier, so gang degenerates to whole-GEMM placement."""
    chip = ChipConfig(n_cores=3, design="RASA-WLBP")
    shards = assign([SMALL, SMALL, SMALL], chip, "gang")
    assert sorted(len(s) for s in shards) == [1, 1, 1]
    assert all(s[0].name == "small" for s in shards)


def test_assign_gang_single_spec():
    """gang with a one-GEMM workload: MACs conserved through whatever
    split it picks; a single-tile GEMM cannot split and lands whole."""
    chip = ChipConfig(n_cores=4, design="RASA-WLBP")
    shards = assign([SMALL], chip, "gang")
    assert sum(s.macs for core in shards for s in core) == SMALL.macs
    tiny = GemmSpec("tiny", 16, 32, 16)         # one hardware tile
    shards = assign([tiny], chip, "gang")
    placed = [s for core in shards for s in core]
    assert len(placed) == 1 and placed[0].macs == tiny.macs
    # n_cores=1: the whole workload, in submission order, on core 0
    one = ChipConfig(n_cores=1, design="RASA-WLBP")
    assert assign([SMALL], one, "gang") == [[SMALL]]


def test_assign_incremental_single_core_reduction():
    """n_cores=1: all items in submission order on core 0 -- exactly the
    work_queue placement."""
    from repro.multicore import assign_incremental
    wl = _skewed_workload()
    one = ChipConfig(n_cores=1, design="RASA-WLBP")
    assert assign_incremental(wl, one, [0.0]) == assign(wl, one,
                                                        "work_queue")
    # any backlog estimate: still core 0, still submission order
    assert assign_incremental(wl, one, [1e9]) == [list(wl)]


def test_assign_incremental_respects_backlog_and_groups():
    """Items go to the soonest-free core given the existing backlog;
    grouped items (a serving request's GEMM chain) stay on one core."""
    from repro.multicore import assign_incremental
    chip = ChipConfig(n_cores=2, design="RASA-WLBP")
    # core 0 is busy forever: everything lands on core 1
    placed = assign_incremental([SMALL, ODD], chip, [math.inf, 0.0])
    assert placed[0] == [] and placed[1] == [SMALL, ODD]
    # a group is atomic and returned as given
    group = (SMALL, ODD)
    placed = assign_incremental([group, SMALL], chip, [0.0, 0.0])
    flat = [item for core in placed for item in core]
    assert group in flat and SMALL in flat
    gcore = next(c for c, items in enumerate(placed) if group in items)
    # the single GEMM went to the other core (the group filled the first)
    assert SMALL in placed[1 - gcore]
    with pytest.raises(ValueError):
        assign_incremental([SMALL], chip, [0.0])    # one entry per core


def test_chip_report_aggregates():
    rep = simulate_chip(SMALL, ChipConfig(n_cores=4, design="RASA-WLBP"))
    assert len(rep.per_core_cycles) == 4
    assert rep.cycles == max(rep.per_core_cycles)
    assert rep.macs == SMALL.macs
    assert 0.0 < rep.utilization <= 1.0
    assert 0.0 <= rep.wlbp_rate <= 1.0
    ref = simulate(SMALL, "RASA-WLBP")
    assert rep.n_mm == ref.n_mm


def test_chip_config_validation():
    with pytest.raises(ValueError):
        ChipConfig(n_cores=0)
    with pytest.raises(ValueError):
        ChipConfig(arbitration="cyclic")
    with pytest.raises(ValueError):
        ChipConfig(epoch_cycles=0.0)
    with pytest.raises(ValueError):
        simulate_chip([], ChipConfig(n_cores=2))
