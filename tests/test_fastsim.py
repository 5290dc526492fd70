"""Fast-backend parity suite: the trace-compiled numpy/jax simulators must
reproduce the reference ``PipelineSimulator`` -- cycles, WL skips, and
bandwidth-stall cycles -- on arbitrary instruction streams, across all eight
designs and both load-model families (idealized ports and epoch token
buckets), plus the chip-level epoch-arbiter fixed point end to end."""

import dataclasses
import math
import random

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import (DESIGNS, GemmSpec, Instr, Op, TABLE_I, get_design,
                        simulate, sweep_designs, sweep_workload)
from repro.core.fastsim import (StreamModelParams, _run_numpy_params,
                                run_cores, run_trace_numpy, sweep_trace)
from repro.core.simulator import _simulate_cached
from repro.core.tiling import ALG1_POLICY, lower_gemm, lowered_stream
from repro.core.timing import LoadStreamModel, PipelineSimulator
from repro.core.trace import compile_stream, compiled_trace, gemm_trace
from repro.multicore import ChipConfig, simulate_chip
from repro.multicore.chip import EpochBandwidthLoadModel

SMALL = GemmSpec("small", 128, 256, 256)
REL = 1e-6          # the acceptance bound; numpy is in fact bit-exact


def random_stream(rng: random.Random, n: int) -> list[Instr]:
    """Random but well-defined stream: all registers TL-defined up front,
    then a mix of loads, stores and MMs (including reuse runs, C-chains,
    and MMs whose destination aliases their B register)."""
    stream = [Instr(Op.TL, dst=r, addr=("B", 0, r)) for r in range(8)]
    for _ in range(n):
        x = rng.random()
        if x < 0.3:
            stream.append(Instr(
                Op.TL, dst=rng.randrange(8),
                addr=(rng.choice("ABC"), rng.randrange(4), rng.randrange(4)),
                tm=rng.choice((1, 7, 16)), tk=rng.choice((8, 32)),
                tn=rng.choice((3, 16))))
        elif x < 0.45:
            stream.append(Instr(
                Op.TS, src1=rng.randrange(8),
                addr=("C", rng.randrange(4), 0),
                tm=rng.choice((1, 16)), tn=rng.choice((3, 16))))
        else:
            b = rng.randrange(8)
            # bias toward repeating B registers so WLBP reuse fires
            if rng.random() < 0.5 and stream[-1].op is Op.MM:
                b = stream[-1].src2
            stream.append(Instr(
                Op.MM, dst=rng.randrange(8), src1=rng.randrange(8),
                src2=b, tm=rng.choice((1, 8, 16))))
    return stream


def make_models():
    """(name, model factory, params) for both load-model families."""
    shares = (8.0, 16.0, 48.0)
    return [
        ("port",
         lambda cfg: LoadStreamModel(cfg.load_ports),
         lambda cfg: StreamModelParams(cfg.load_ports)),
        ("epoch",
         lambda cfg: EpochBandwidthLoadModel(
             cfg.load_ports, shares, 256.0, tail_share=64.0,
             burst_bytes=2048.0, store_ports=1, charge_store_bytes=True),
         lambda cfg: StreamModelParams(
             cfg.load_ports, 1, shares, 256.0, 64.0, 2048.0, True)),
        ("static",
         lambda cfg: EpochBandwidthLoadModel(
             cfg.load_ports, (), math.inf, tail_share=12.0,
             burst_bytes=1024.0, store_ports=1, charge_store_bytes=True),
         lambda cfg: StreamModelParams(
             cfg.load_ports, 1, (), math.inf, 12.0, 1024.0, True)),
    ]


def assert_matches(ref, fast, tag=""):
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL), tag
    assert fast.wl_skips == ref.wl_skips, tag
    assert fast.bw_stall_cycles == pytest.approx(
        ref.bw_stall_cycles, rel=REL, abs=1e-6), tag
    assert (fast.n_mm, fast.n_tl, fast.n_ts) == (ref.n_mm, ref.n_tl,
                                                 ref.n_ts), tag
    assert fast.useful_macs == pytest.approx(ref.useful_macs), tag


def _check_stream(stream, designs=None, jax_too=False):
    trace = compile_stream(stream)
    for design in (designs or sorted(DESIGNS)):
        cfg = get_design(design)
        for name, mk_model, mk_params in make_models():
            ref = PipelineSimulator(cfg, load_model=mk_model(cfg)).run(stream)
            tag = f"{design}/{name}"
            # numpy over live model objects (bit-exact by construction)
            fast = run_trace_numpy(trace, cfg, mk_model(cfg))
            assert fast.cycles == ref.cycles, tag
            assert_matches(ref, fast, tag)
            # numpy with inlined stream-model arithmetic
            inl, _ = _run_numpy_params(trace, cfg, mk_params(cfg))
            assert_matches(ref, inl, tag + "/inline")
            if jax_too:
                jx = sweep_trace(trace, [cfg], mk_params(cfg),
                                 backend="jax")[0]
                assert_matches(ref, jx, tag + "/jax")


# ----------------------------------------------------------- fixed streams
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_numpy_parity_random_streams(seed):
    """All 8 designs x all load models on seeded random streams (numpy)."""
    _check_stream(random_stream(random.Random(seed), 120))


@pytest.mark.parametrize("seed", [0, 5])
def test_jax_parity_random_streams(seed):
    """jax scan parity on random streams (two designs to bound compiles)."""
    _check_stream(random_stream(random.Random(seed), 90),
                  designs=["RASA-WLBP", "RASA-DMDB-WLS"], jax_too=True)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 9), st.integers(1, 200),
       st.sampled_from(sorted(DESIGNS)))
def test_parity_property(seed, n, design):
    """Hypothesis: fast == reference on arbitrary streams and designs."""
    _check_stream(random_stream(random.Random(seed), n), designs=[design])


def test_static_reuse_bits_match_dirty_bit_tracking():
    """The trace's precompiled WLBP reuse bits equal the runtime dirty-bit
    decisions, including when an MM's destination aliases its B register."""
    stream = [
        Instr(Op.TL, dst=7, addr=("B", 0, 0)),
        Instr(Op.TL, dst=4, addr=("A", 0, 0)),
        Instr(Op.MM, dst=0, src1=4, src2=7, tm=16),
        Instr(Op.MM, dst=1, src1=4, src2=7, tm=16),   # reuse
        Instr(Op.MM, dst=7, src1=4, src2=7, tm=16),   # C aliases B
        Instr(Op.MM, dst=1, src1=4, src2=7, tm=16),   # still reusable
        Instr(Op.TL, dst=7, addr=("B", 0, 1)),        # overwrite weights
        Instr(Op.MM, dst=2, src1=4, src2=7, tm=16),   # must reload
    ]
    trace = compile_stream(stream)
    mm_bits = [bool(b) for o, b in zip(trace.opcode, trace.reusable)
               if o == 2]
    assert mm_bits == [False, True, True, True, False]
    cfg = get_design("RASA-WLBP")
    ref = PipelineSimulator(cfg).run(stream)
    assert ref.wl_skips == sum(mm_bits)
    assert run_trace_numpy(trace, cfg).wl_skips == ref.wl_skips


# ------------------------------------------------------------ GEMM parity
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_simulate_backend_parity(backend):
    ref = simulate(SMALL, "RASA-DMDB-WLS")
    fast = simulate(SMALL, "RASA-DMDB-WLS", backend=backend)
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL)
    assert fast.wl_skips == ref.wl_skips
    assert fast.utilization == pytest.approx(ref.utilization, rel=REL)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sweep_designs_backend_parity(backend):
    ref = sweep_designs(SMALL)
    fast = sweep_designs(SMALL, backend=backend)
    assert set(ref) == set(fast)
    for k in ref:
        assert fast[k].cycles == pytest.approx(ref[k].cycles, rel=REL), k
        assert fast[k].wl_skips == ref[k].wl_skips, k


def test_sweep_workload_grid_parity():
    wl = [SMALL, TABLE_I["DLRM-2"], GemmSpec("odd", 200, 96, 150)]
    ref = sweep_workload(wl)
    fast = sweep_workload(wl, backend="jax")
    for r, f in zip(ref, fast):
        for k in r:
            assert f[k].cycles == pytest.approx(r[k].cycles, rel=REL), k
            assert f[k].wl_skips == r[k].wl_skips, k


def test_simulate_custom_load_model_falls_back_to_reference():
    """A load model the fast backends cannot express must still be honored
    (silent fallback to the reference loop), not ignored."""
    class Throttled(LoadStreamModel):
        def acquire(self, t_request, n_bytes):
            start, stall = super().acquire(t_request, n_bytes)
            return start + 100.0, stall

    ref = simulate(SMALL, "RASA-WLBP", load_model=Throttled(2))
    fast = simulate(SMALL, "RASA-WLBP", load_model=Throttled(2),
                    backend="fast")
    assert fast.cycles == ref.cycles
    assert fast.cycles > simulate(SMALL, "RASA-WLBP").cycles


# ----------------------------------------------------- caching satellites
def test_simulate_cached_accepts_frozen_engine_config():
    import dataclasses
    cfg = dataclasses.replace(get_design("RASA-WLBP"), name="probe",
                              load_latency=11)
    _simulate_cached.cache_clear()
    a = _simulate_cached(SMALL, cfg, ALG1_POLICY)
    before = _simulate_cached.cache_info().hits
    b = _simulate_cached(SMALL, cfg, ALG1_POLICY)
    assert a is b
    assert _simulate_cached.cache_info().hits == before + 1


def test_lowered_stream_memoized():
    s1 = lowered_stream(SMALL, ALG1_POLICY)
    s2 = lowered_stream(SMALL, ALG1_POLICY)
    assert s1 is s2
    assert list(s1) == list(lower_gemm(SMALL, ALG1_POLICY))


def test_compiled_trace_cached_and_consistent():
    t1 = compiled_trace((SMALL,), ALG1_POLICY)
    t2 = gemm_trace(SMALL, ALG1_POLICY)
    assert t1 is t2
    assert t1.n_mm + t1.n_tl + t1.n_ts == len(t1)
    assert t1.n_mm == sum(1 for i in lowered_stream(SMALL, ALG1_POLICY)
                          if i.op is Op.MM)


# ------------------------------------------------- chip-level arbiter parity
def _skewed():
    return [TABLE_I["DLRM-2"], SMALL, SMALL, SMALL, SMALL, SMALL]


@pytest.mark.parametrize("arbitration", ["static", "epoch"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_chip_backend_parity(arbitration, backend):
    """run_streams fixed point: fast backends match the reference chip
    simulation -- makespan, stalls, arbiter trace -- under a binding
    budget."""
    mk = lambda be: simulate_chip(
        _skewed(), ChipConfig(n_cores=2, design="RASA-WLBP",
                              bw_bytes_per_cycle=24.0,
                              arbitration=arbitration, backend=be),
        scheduler="work_queue")
    ref, fast = mk("reference"), mk(backend)
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL)
    assert fast.bw_stall_cycles == pytest.approx(ref.bw_stall_cycles,
                                                 rel=REL, abs=1e-6)
    assert fast.wl_skips == ref.wl_skips
    assert fast.n_mm == ref.n_mm
    assert fast.arb_rounds == ref.arb_rounds
    assert fast.share_trace == pytest.approx(ref.share_trace)
    assert fast.active_trace == ref.active_trace


def test_run_cores_epoch_parity_with_last_grant():
    """Batched run_cores reproduces per-core reference runs of the epoch
    bucket exactly, including the activity horizon (last_grant)."""
    cfg = get_design("RASA-WLBP")
    shares = (8.0, 12.0, 24.0)
    specs = [SMALL, GemmSpec("odd", 200, 96, 150)]
    streams = [lowered_stream(s, ALG1_POLICY) for s in specs]
    traces = [compiled_trace((s,), ALG1_POLICY) for s in specs]
    tails = (24.0, 48.0)
    params = [StreamModelParams(cfg.load_ports, 1, shares, 1024.0, t,
                                2048.0, True) for t in tails]
    refs = []
    for s, t in zip(streams, tails):
        m = EpochBandwidthLoadModel(cfg.load_ports, shares, 1024.0, t,
                                    2048.0, 1, True)
        r = PipelineSimulator(cfg, load_model=m).run(s)
        refs.append((r, m.last_grant))
    backends = ["numpy", "jax"]
    for be in backends:
        for (rr, rlg), (fr, flg) in zip(
                refs, run_cores(traces, cfg, params, backend=be)):
            assert fr.cycles == pytest.approx(rr.cycles, rel=REL), be
            assert fr.wl_skips == rr.wl_skips, be
            assert flg == pytest.approx(rlg, rel=REL), be


# ------------------------------------------------- arbiter short-circuit
def test_arbiter_records_skipped_rounds():
    """The epoch relaxation skips cores whose visible share schedule is
    unchanged, records them per round, and still converges to the same
    fixed point as the skip-free reference backend."""
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=32.0)
    wl = [TABLE_I["DLRM-2"], TABLE_I["BERT-1"], TABLE_I["DLRM-2"],
          TABLE_I["BERT-1"], TABLE_I["DLRM-2"], TABLE_I["DLRM-2"]]
    fast = simulate_chip(wl, chip, scheduler="lpt")
    ref = simulate_chip(wl, dataclasses.replace(chip, backend="reference"),
                        scheduler="lpt")
    assert fast.cycles == pytest.approx(ref.cycles, rel=REL)
    assert len(fast.arb_skipped) == fast.arb_rounds
    assert fast.arb_skipped[0] == 0           # round 1 simulates everyone
    assert sum(fast.arb_skipped) > 0          # later rounds skip someone
    # the reference path never skips (it is the oracle)
    assert ref.arb_skipped == (0,) * ref.arb_rounds


def test_single_core_fast_equals_reference_chip():
    """n=1 chip reduction holds on every backend."""
    ref = simulate(SMALL, "RASA-DMDB-WLS")
    for be in ("reference", "numpy", "fast"):
        rep = simulate_chip(SMALL, ChipConfig(n_cores=1,
                                              design="RASA-DMDB-WLS",
                                              backend=be))
        assert rep.cycles == pytest.approx(ref.cycles, rel=REL), be
        assert rep.bw_stall_cycles == 0.0, be


# ------------------------------------------------ resumable segment runner
def test_run_segment_resume_parity():
    """Resuming the inlined numpy recurrence from a snapshot is bit-exact:
    under the unchanged schedule from any snapshot, and under a schedule
    whose shares changed only past the snapshot's horizon -- the invariant
    the online chip model's re-simulation path rests on."""
    from repro.core.fastsim import run_segment
    cfg = get_design("RASA-WLBP")
    E = 2048.0
    stream = random_stream(random.Random(11), 1500)
    trace = compile_stream(stream)
    shares_a = tuple([6.0, 9.0, 12.0, 18.0, 24.0, 32.0] * 6)
    pa = StreamModelParams(cfg.load_ports, 1, shares_a, E, 64.0,
                           2048.0, True)
    ra, lga, snaps = run_segment(trace, cfg, pa, snap_stride=128)
    assert snaps
    for s1, s2 in zip(snaps, snaps[1:]):
        assert s2.i > s1.i and s2.horizon >= s1.horizon
    for s in snaps[::4]:
        r2, lg2, _ = run_segment(trace, cfg, pa, carry=s)
        assert (r2.cycles, lg2, r2.wl_skips, r2.bw_stall_cycles) == \
            (ra.cycles, lga, ra.wl_skips, ra.bw_stall_cycles), s.i
    resumed_any = False
    for x in (8, 16, 24):
        shares_b = shares_a[:x] + tuple(v * 0.5 for v in shares_a[x:])
        pb = StreamModelParams(cfg.load_ports, 1, shares_b, E, 48.0,
                               2048.0, True)
        rb, lgb, _ = run_segment(trace, cfg, pb)
        model = EpochBandwidthLoadModel(cfg.load_ports, shares_b, E, 48.0,
                                        2048.0, 1, True)
        ref = PipelineSimulator(cfg, load_model=model).run(stream)
        assert rb.cycles == ref.cycles and lgb == model.last_grant, x
        usable = [s for s in snaps if s.horizon <= x * E]
        if not usable:
            continue
        resumed_any = True
        r2, lg2, _ = run_segment(trace, cfg, pb, carry=usable[-1])
        assert (r2.cycles, lg2, r2.wl_skips, r2.bw_stall_cycles) == \
            (rb.cycles, lgb, rb.wl_skips, rb.bw_stall_cycles), x
    assert resumed_any          # the scenario must actually exercise resume


# ------------------------------------------------ online chip parity
def _online_scenario(backend):
    """Staggered arrivals + a queued mid-run injection on a tight budget."""
    from repro.multicore import OnlineChip
    chip = ChipConfig(n_cores=2, design="RASA-WLBP",
                      bw_bytes_per_cycle=24.0, backend=backend)
    oc = OnlineChip(chip, snap_stride=512)
    segs = [oc.submit(0, [TABLE_I["DLRM-2"]])]
    oc.advance_to(2)
    segs.append(oc.submit(1, [SMALL]))               # arrival mid-run
    oc.advance_to(4)
    segs.append(oc.submit(0, [GemmSpec("odd", 200, 96, 150)]))  # queued
    segs.append(oc.submit(1, [SMALL]))
    oc.drain()
    return oc, segs


def test_online_chip_backend_parity():
    """Every arrival/departure of the online scenario lands identically on
    the reference, numpy and fast backends: per-segment finish times,
    makespan, and the converged share/active traces."""
    ref, rsegs = _online_scenario("reference")
    for be in ["numpy", "fast"]:
        oc, segs = _online_scenario(be)
        assert oc.makespan == pytest.approx(ref.makespan, rel=REL), be
        for s, rs in zip(segs, rsegs):
            assert oc.finish_time(s) == pytest.approx(
                ref.finish_time(rs), rel=REL), (be, s.sid)
            assert (s.start, s.end) == (rs.start, rs.end), (be, s.sid)
        assert oc.share_trace == pytest.approx(ref.share_trace), be
        assert oc.active_trace == ref.active_trace, be
        # the fast path must actually resume from snapshots, not replay
        assert oc.stats["sims_resumed"] > 0, be
        assert oc.stats["instrs_resumed_past"] > 0, be
