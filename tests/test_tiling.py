"""Lowering (GEMM -> RASA stream) tests: correctness for every policy,
edge tiles, instruction counts, and reuse properties."""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ALG1_POLICY, MAX_REUSE_POLICY, GemmSpec, Op,
                        RegPolicy, count_ops, lower_gemm, stream_stats,
                        validate_stream)
from repro.core.tiling import LOW_REUSE_POLICY
from repro.core.engine import reference_gemm, run_gemm
from repro.core.isa import TILE_K, TILE_M, TILE_N


POLICIES = {
    "alg1": ALG1_POLICY,
    "max_reuse": MAX_REUSE_POLICY,
    "low_reuse": LOW_REUSE_POLICY,
    "tall": RegPolicy(mc=4, nc=1, a_regs=2, b_regs=1),
    "wide": RegPolicy(mc=1, nc=4, a_regs=1, b_regs=2),
    "pressure": RegPolicy(mc=3, nc=2, a_regs=1, b_regs=1),
}


@pytest.mark.parametrize("policy", POLICIES.values(), ids=POLICIES.keys())
@pytest.mark.parametrize("shape", [(16, 32, 16), (32, 32, 32), (48, 96, 64),
                                   (17, 33, 15), (3, 2, 1), (100, 64, 40)])
def test_lowering_correct(policy, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    a = rng.normal(size=(m, k)).astype(np.float32)
    b = rng.normal(size=(k, n)).astype(np.float32)
    c = rng.normal(size=(m, n)).astype(np.float32)
    got = run_gemm(a, b, c, policy=policy)
    want = reference_gemm(a, b, c)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_alg1_matches_paper_example():
    """Algorithm 1: a 32x32x32 GEMM uses 4 C loads, 2 A + 2 B loads, 4 MMs,
    4 stores -- and the B register is reused on MMs 2 and 4."""
    spec = GemmSpec("alg1", 32, 32, 32)
    stream = list(lower_gemm(spec, ALG1_POLICY))
    ops = count_ops(stream)
    assert ops == {"tl": 8, "ts": 4, "mm": 4}
    stats = stream_stats(spec, ALG1_POLICY)
    assert stats["wlbp_hits"] == 2 and stats["wlbp_rate"] == 0.5


def test_mm_count_formula():
    spec = GemmSpec("x", 100, 70, 40)
    stats = stream_stats(spec)
    assert stats["mm"] == math.ceil(100 / TILE_M) * math.ceil(70 / TILE_K) * math.ceil(40 / TILE_N)


def test_reuse_rates():
    spec = GemmSpec("x", 256, 256, 256)
    assert stream_stats(spec, ALG1_POLICY)["wlbp_rate"] == pytest.approx(0.5, abs=0.01)
    # 240 = 15 M-tiles = 3 full mc=5 blocks -> exact (mc-1)/mc rate
    spec5 = GemmSpec("x", 240, 256, 256)
    assert stream_stats(spec5, MAX_REUSE_POLICY)["wlbp_rate"] == pytest.approx(0.8, abs=0.01)
    assert stream_stats(spec, LOW_REUSE_POLICY)["wlbp_rate"] == 0.0


def test_exact_tiles_shorten_ff():
    """Beyond-paper: AMX-tilecfg exact edge tiles reduce cycles vs padded."""
    from repro.core import simulate
    spec = GemmSpec("b1", 1, 512, 512)      # batch 1: tm=1 with exact tiles
    padded = simulate(spec, "BASE", RegPolicy())
    exact = simulate(spec, "BASE", RegPolicy(pad_tiles=False))
    assert exact.cycles < padded.cycles


def test_stream_is_valid():
    for policy in POLICIES.values():
        validate_stream(lower_gemm(GemmSpec("v", 33, 65, 47), policy))


def test_policy_register_budget():
    with pytest.raises(ValueError):
        RegPolicy(mc=4, nc=2, a_regs=2, b_regs=2)   # 12 > 8


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 80), st.integers(1, 96), st.integers(1, 64),
       st.sampled_from(list(POLICIES.values())))
def test_lowering_correct_property(m, k, n, policy):
    """Property: lowering + functional engine == mixed-precision reference
    for arbitrary GEMM dims and any register policy."""
    rng = np.random.default_rng(m * 10007 + k * 101 + n)
    a = rng.normal(size=(m, k)).astype(np.float32)
    b = rng.normal(size=(k, n)).astype(np.float32)
    c = rng.normal(size=(m, n)).astype(np.float32)
    got = run_gemm(a, b, c, policy=policy)
    want = reference_gemm(a, b, c)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
