"""Cycle-model tests: every number the paper states, plus pipeline invariants."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DESIGNS, Instr, Op, get_design,
                        steady_state_interval)
from repro.core.designs import EngineConfig
from repro.core.timing import PipelineSimulator, serial_mm_latency


def mm_stream(n, *, same_b=False, n_c=4, tm=16):
    """An ideal rasa_mm stream: operands preloaded (ready at t=0)."""
    out = []
    for i in range(n):
        b = 7 if same_b else 6 + (i % 2)
        out.append(Instr(Op.MM, dst=i % n_c, src1=4 + (i % 2), src2=b, tm=tm))
    return out


# ---------------------------------------------------------------- paper facts
def test_baseline_latency_is_95():
    """Paper §V: 'L_baseline = 95 cycles for the configuration in our
    evaluation' -- 32x16 array, T_M=16."""
    assert get_design("BASE").serial_latency(16) == 95
    assert serial_mm_latency(32, 16, 16) == 95


def test_toy_2x2_utilization():
    """Paper Fig. 1: 2x2 WS array on a 2x2 GEMM -> 7 cycles, 28.6% util."""
    toy = EngineConfig(name="toy", rows=2, cols=2)
    res = PipelineSimulator(toy).run(
        [Instr(Op.MM, dst=0, src1=1, src2=2, tm=2, tk=2, tn=2)])
    assert res.cycles == 7
    assert res.utilization == pytest.approx(2 / 7, abs=1e-6)


def test_eq1_inactive_time():
    """Eq. (2): each PE is inactive Latency_tot - T_M cycles."""
    cfg = get_design("BASE")
    res = PipelineSimulator(cfg).run(mm_stream(1))
    assert res.cycles - 16 == 95 - 16


def test_dmdb_wls_asymptote():
    """Paper §V: perfectly pipelined rasa_mm every 16 cycles -> 16/95."""
    cfg = get_design("RASA-DMDB-WLS")
    base = get_design("BASE")
    n = 2000
    t_d = PipelineSimulator(cfg).run(mm_stream(n)).cycles
    t_b = PipelineSimulator(base).run(mm_stream(n)).cycles
    assert t_d / t_b == pytest.approx(16 / 95, rel=0.01)


def test_pipe_interval_is_wl_ff_fs():
    """PIPE overlaps WL with prior DR: steady interval 2*T_K + T_M - 1 = 79."""
    cfg = get_design("RASA-PIPE")
    r = PipelineSimulator(cfg, keep_schedules=True).run(mm_stream(10))
    s = r.schedules
    assert s[-1].ff_start - s[-2].ff_start == pytest.approx(79)
    assert steady_state_interval(cfg, 16, False) == 79


def test_wlbp_reuse_interval_is_tm():
    cfg = get_design("RASA-WLBP")
    r = PipelineSimulator(cfg, keep_schedules=True).run(mm_stream(10, same_b=True))
    s = r.schedules
    assert s[-1].ff_start - s[-2].ff_start == pytest.approx(16)
    assert s[-1].wl_skipped


def test_wlbp_no_reuse_degrades_to_pipe():
    cfg = get_design("RASA-WLBP")
    pipe = get_design("RASA-PIPE")
    stream = mm_stream(50)  # alternating B registers, never reusable
    a = PipelineSimulator(cfg).run(stream).cycles
    b = PipelineSimulator(pipe).run(stream).cycles
    assert a == b


def test_dirty_bit_blocks_reuse():
    """A tile load to the weight register between rasa_mm must force WL."""
    cfg = get_design("RASA-WLBP")
    stream = [
        Instr(Op.MM, dst=0, src1=4, src2=7, tm=16),
        Instr(Op.TL, dst=7, addr=("B", 0, 1)),       # overwrite weights
        Instr(Op.MM, dst=1, src1=4, src2=7, tm=16),
    ]
    r = PipelineSimulator(cfg, keep_schedules=True).run(stream)
    assert not r.schedules[1].wl_skipped
    assert r.wl_skips == 0


def test_db_wls_hides_weight_load():
    """DB-WLS sustains interval T_M even without weight reuse, as long as
    the WL port keeps up (interval >= WL/1 port => T_K for fresh weights)."""
    cfg = get_design("RASA-DMDB-WLS")   # rows=16 -> WL=16 fits under T_M=16
    r = PipelineSimulator(cfg, keep_schedules=True).run(mm_stream(100))
    s = r.schedules
    assert s[-1].ff_start - s[-2].ff_start == pytest.approx(16)


def test_wl_port_serializes_fresh_weights():
    """With 32 rows, back-to-back *fresh* weight sets cannot beat one WL (32
    cycles) per instruction even with DB-WLS -- the insertion network is a
    single resource."""
    cfg = get_design("RASA-DB-WLS")
    r = PipelineSimulator(cfg, keep_schedules=True).run(mm_stream(100))
    s = r.schedules
    assert s[-1].ff_start - s[-2].ff_start == pytest.approx(32)


def test_c_register_dependency_serializes():
    """Chained accumulation into one C register must wait for the drain --
    the reason Algorithm 1 round-robins four C tiles."""
    cfg = get_design("RASA-DMDB-WLS")
    chained = PipelineSimulator(cfg).run(mm_stream(50, n_c=1)).cycles
    rotated = PipelineSimulator(cfg).run(mm_stream(50, n_c=4)).cycles
    assert chained > 2 * rotated


def test_dm_halves_rows():
    cfg = get_design("RASA-DM-WLBP")
    assert cfg.rows == 16 and cfg.macs_per_pe == 2
    assert cfg.peak_macs_per_cycle == get_design("BASE").peak_macs_per_cycle


def test_wls_requires_db():
    with pytest.raises(ValueError):
        EngineConfig(name="bad", wls=True, double_buffer=False)


@pytest.mark.parametrize("design", sorted(DESIGNS))
@pytest.mark.parametrize("reused", [False, True])
def test_steady_state_interval_matches_simulator(design, reused):
    """The analytic issue-to-issue interval must agree with the simulated
    back-to-back rasa_mm interval for every design, with and without
    weight-register reuse."""
    cfg = get_design(design)
    r = PipelineSimulator(cfg, keep_schedules=True).run(
        mm_stream(200, same_b=reused))
    s = r.schedules
    measured = s[-1].ff_start - s[-2].ff_start
    # reuse only fires on WLBP designs; the analytic form takes the
    # *effective* reuse the dirty-bit tracking would see.
    effective_reuse = reused and cfg.wlbp
    assert measured == pytest.approx(
        steady_state_interval(cfg, 16, effective_reuse)), design


# ---------------------------------------------------------- pipeline invariants
@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 1), st.integers(0, 1)),
                min_size=1, max_size=60),
       st.sampled_from(sorted(DESIGNS)))
def test_schedule_monotone_and_ordered(ops, design):
    """Property: for every design and stream, (i) stages of one instruction
    are ordered WL<=FF<FS<DR, (ii) FF starts never decrease (in-order array),
    (iii) no design is slower than BASE on the same stream."""
    stream = [Instr(Op.MM, dst=c, src1=4 + a, src2=6 + b, tm=16)
              for c, a, b in ops]
    cfg = get_design(design)
    r = PipelineSimulator(cfg, keep_schedules=True).run(stream)
    prev_ff = -1.0
    for s in r.schedules:
        assert s.wl_start <= s.ff_start
        assert s.ff_start < s.ff_end <= s.fs_end <= s.dr_end
        assert s.ff_start >= prev_ff
        prev_ff = s.ff_start
    base = PipelineSimulator(get_design("BASE")).run(stream)
    assert r.cycles <= base.cycles + 1e-9


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 200))
def test_throughput_bounds(n):
    """No design may exceed peak: useful MACs <= cycles * peak."""
    for design in DESIGNS:
        cfg = get_design(design)
        r = PipelineSimulator(cfg).run(mm_stream(n))
        assert r.useful_macs <= r.cycles * cfg.peak_macs_per_cycle + 1e-6
        assert 0.0 <= r.utilization <= 1.0
