"""Jitted whole-trace arbitration parity suite.

``repro.multicore.jitarb`` lowers the serving batcher's entire online
run -- span arbitration *and* admission -- into one XLA program; on its
domain (``fixed`` admission at any batch size, the reactive
``occupancy``/``bandwidth``/``predicted`` policies, equal or
demand-weighted shares, homogeneous or mixed fault-free chips) the
``BatchReport`` must be **bit-identical** -- not approximately equal --
to the numpy incremental client.  Pinned here:

* in-domain parity across all eight designs (equal and demand-weighted
  shares), workload shapes, core counts, bandwidths, heterogeneous
  BASE/RASA mixes, batch sizes, every reactive admission policy, and a
  real-model (``model_trace``) request stream;
* the ``plan_ex`` gate: every out-of-domain configuration (active
  ``FaultPlan``, ``phase_aware`` admission, non-power-of-two epochs)
  returns a structured reason, ``run_batcher`` still answers through
  the incremental-client fallback agreeing with ``backend="fast"``,
  and the reason surfaces on ``BatchReport.jit_gate``;
* the vmapped sweep (``plan_many``/``finish_times_many``) agreeing with
  per-variant sequential runs;
* hypothesis properties: random small traces, and window-size
  independence (the sliding settled-prefix window is an implementation
  tile -- growing it must not move a single bit).

Everything is exact equality on purpose: the jitted program replays the
same share expressions and the same token-bucket arithmetic, so any ulp
of drift is a bug (see the FMA note in ``repro.core.fastsim``).
"""

import dataclasses

import pytest

from hypothesis import given, settings, strategies as st
from repro.multicore import ChipConfig
from repro.multicore.faults import FaultPlan, core_down, core_up
from repro.multicore.jitarb import (finish_admit_times, finish_times_many,
                                    plan, plan_ex, plan_many)
from repro.serving.simbatch import (model_trace, report_from_finishes,
                                    run_batcher, synthetic_trace)

ALL_DESIGNS = ("BASE", "RASA-DB-WLBP", "RASA-DB-WLS", "RASA-DM-PIPE",
               "RASA-DM-WLBP", "RASA-DMDB-WLS", "RASA-PIPE", "RASA-WLBP")
REACTIVE = ("occupancy", "bandwidth", "predicted")


def _trace(n=10, seed=1, mean_gap=2, d_model=128, **kw):
    kw.setdefault("prompt_lens", (16, 32))
    kw.setdefault("decode_steps", (1, 2))
    kw.setdefault("decode_batch", 8)
    return synthetic_trace(n, seed=seed, mean_gap=mean_gap,
                           d_model=d_model, **kw)


def _chips(**kw):
    kw.setdefault("n_cores", 2)
    kw.setdefault("design", "RASA-WLBP")
    kw.setdefault("bw_bytes_per_cycle", 32.0)
    fast = ChipConfig(backend="fast", **kw)
    return fast, dataclasses.replace(fast, backend="jax")


def _hetero_chips(cores=("BASE", "RASA-WLBP"), **kw):
    kw.setdefault("bw_bytes_per_cycle", 32.0)
    fast = ChipConfig(backend="fast", n_cores=None, design=None,
                      cores=cores, **kw)
    return fast, dataclasses.replace(fast, backend="jax")


def _traffic(requests):
    return [(r.arrival_epoch, r.specs) for r in requests]


def _assert_identical(requests, fast, jax_chip, **batcher_kw):
    batcher_kw.setdefault("policy", "fixed")
    batcher_kw.setdefault("batch_size", 1)
    a = run_batcher(requests, fast, **batcher_kw)
    b = run_batcher(requests, jax_chip, **batcher_kw)
    assert a == b           # bit-identical BatchReport, every field
    assert b.jit_gate is None   # the jitted lane actually served it
    return a


def _assert_in_domain(requests, jax_chip, **plan_kw):
    p, why = plan_ex(_traffic(requests), jax_chip, **plan_kw)
    assert p is not None, f"unexpected gate: {why}"
    return p


# ------------------------------------------------------ in-domain parity
@pytest.mark.parametrize("design", ALL_DESIGNS)
def test_all_designs_bit_identical(design):
    """Every design's jitted settle equals the numpy client exactly --
    per-design grant rules (WLBP skips, WLS, double-buffering, pipe
    overlap) all flow through the same shared scan program."""
    fast, jx = _chips(design=design)
    requests = _trace(8, seed=3)
    _assert_in_domain(requests, jx)
    _assert_identical(requests, fast, jx)


@pytest.mark.parametrize("design", ALL_DESIGNS)
def test_demand_shares_bit_identical(design):
    """Demand-weighted shares jit: float span weights fold in the host
    arbiter's span order, so grants are summation-order-stable and every
    design agrees bit-for-bit."""
    fast, jx = _chips(design=design, share_policy="demand")
    requests = _trace(8, seed=7, mean_gap=1)
    _assert_in_domain(requests, jx)
    _assert_identical(requests, fast, jx)


@pytest.mark.parametrize("kw", [
    dict(n_cores=1),
    dict(n_cores=3, bw_bytes_per_cycle=48.0),
    dict(n_cores=4, bw_bytes_per_cycle=16.0),   # bandwidth-starved
], ids=["one-core", "three-core", "starved"])
def test_shapes_and_contention_bit_identical(kw):
    fast, jx = _chips(**kw)
    requests = _trace(12, seed=4, mean_gap=1)   # overlapping spans
    _assert_identical(requests, fast, jx)


def test_burst_arrivals_bit_identical():
    """All requests in one epoch: every boundary coincides, the deepest
    relaxation case."""
    fast, jx = _chips(n_cores=2)
    requests = _trace(8, seed=6, mean_gap=0)
    _assert_identical(requests, fast, jx)


@pytest.mark.parametrize("policy", REACTIVE)
def test_reactive_admission_bit_identical(policy):
    """The reactive policies run *inside* the while_loop -- headroom,
    occupancy, soonest-free placement and work conservation all replayed
    from carried state -- and agree with the host driver exactly,
    admit epochs included."""
    fast, jx = _chips(n_cores=2)
    requests = _trace(10, seed=12, mean_gap=1)
    _assert_in_domain(requests, jx, policy=policy)
    _assert_identical(requests, fast, jx, policy=policy)


@pytest.mark.parametrize("batch_size", (2, 3, 8))
def test_fixed_batch_sizes_bit_identical(batch_size):
    """``fixed`` admission at any batch size is a closed form of the
    arrival order (group flush epochs): no in-program decisions, still
    bit-identical -- admit epochs included."""
    fast, jx = _chips(n_cores=2)
    requests = _trace(9, seed=13, mean_gap=1)
    _assert_in_domain(requests, jx, batch_size=batch_size)
    _assert_identical(requests, fast, jx, batch_size=batch_size)


@pytest.mark.parametrize("policy", ("fixed", "bandwidth"))
def test_heterogeneous_mix_bit_identical(policy):
    """Mixed BASE/RASA chips jit end-to-end: engine design scalars and
    port rates ride the lane axis of the vmapped simulate chunk, and
    per-(shape, core) trace rows, weights and cost estimates enter as
    tables."""
    fast, jx = _hetero_chips()
    requests = _trace(8, seed=8, mean_gap=1)
    _assert_in_domain(requests, jx, policy=policy)
    _assert_identical(requests, fast, jx, policy=policy)


def test_hetero_rasa_mix_bit_identical():
    """A second mixed pair (pipelined vs WLBP RASA cores): per-core
    tiling policies compile distinct trace rows for the same request
    shape, and the per-(shape, core) row table routes each lane to its
    own columns."""
    fast, jx = _hetero_chips(cores=("RASA-WLBP", "RASA-PIPE"))
    requests = _trace(6, seed=14)
    _assert_in_domain(requests, jx)
    _assert_identical(requests, fast, jx)


def test_model_trace_bit_identical():
    """Real-model request streams (compiled per-layer prefill + decode
    GEMM chains) stay inside the domain and agree exactly."""
    requests = model_trace("gemma-2b", 6, seed=2, mean_gap=2,
                           prompt_lens=(32,), decode_steps=(1, 2))
    fast, jx = _chips(n_cores=2, bw_bytes_per_cycle=48.0)
    _assert_in_domain(requests, jx)
    _assert_identical(requests, fast, jx)


@pytest.mark.parametrize("policy", REACTIVE)
def test_model_trace_reactive_bit_identical(policy):
    """Reactive admission on the real-model stream: the full serving
    frontend (model configs -> GEMM chains -> reactive batcher) through
    the jitted program."""
    requests = model_trace("gemma-2b", 6, seed=2, mean_gap=1,
                           prompt_lens=(32,), decode_steps=(1, 2))
    fast, jx = _chips(n_cores=2, bw_bytes_per_cycle=48.0)
    _assert_in_domain(requests, jx, policy=policy)
    _assert_identical(requests, fast, jx, policy=policy)


def test_vmapped_sweep_matches_sequential():
    """An arrival-rate sweep settled as ONE vmapped launch equals the
    per-variant sequential runs."""
    base = _trace(8, seed=5, mean_gap=3)
    fast, jx = _chips(n_cores=2)
    variants = [[dataclasses.replace(r, arrival_epoch=int(r.arrival_epoch
                                                          * f))
                 for r in base] for f in (1.0, 0.5, 0.0)]
    plans = plan_many([_traffic(v) for v in variants], jx)
    assert plans is not None
    outs = finish_times_many(plans)
    for v, fin in zip(variants, outs):
        want = run_batcher(v, fast, policy="fixed", batch_size=1)
        assert report_from_finishes(v, jx, fin) == want


# ------------------------------------------------- plan gate + fallback
def test_gate_active_fault_plan_falls_back():
    fp = FaultPlan((core_down(0, 2), core_up(0, 12)))
    fast, jx = _chips(n_cores=2, fault_plan=fp)
    requests = _trace(6, seed=9)
    assert plan_ex(_traffic(requests), jx)[1] == "faults_active"
    a = run_batcher(requests, fast)
    b = run_batcher(requests, jx)
    assert a == b
    assert b.jit_gate == "faults_active"    # fallback is diagnosable

    # the *empty* plan is a no-op by construction and stays in-domain
    fast0, jx0 = _chips(n_cores=2, fault_plan=FaultPlan())
    assert plan(_traffic(requests), jx0) is not None
    _assert_identical(requests, fast0, jx0)


def test_gate_unsupported_policy_falls_back():
    """``phase_aware`` keeps its host-only implementation: plan_ex names
    the gate and the fallback still matches fast."""
    fast, jx = _chips(n_cores=2)
    requests = _trace(6, seed=10)
    assert plan_ex(_traffic(requests), jx,
                   policy="phase_aware")[1] == "admission_policy"
    a = run_batcher(requests, fast, policy="phase_aware", batch_size=1)
    b = run_batcher(requests, jx, policy="phase_aware", batch_size=1)
    assert a == b
    assert b.jit_gate == "admission_policy"


def test_gate_reasons_are_structured():
    requests = _trace(4, seed=11)
    fast, jx = _chips()
    assert plan_ex(_traffic(requests), fast)[1] == "backend"
    odd = dataclasses.replace(jx, epoch_cycles=1000.0)  # not a power of 2
    assert plan_ex(_traffic(requests), odd)[1] == "epoch_not_pow2"
    assert plan_ex([], jx)[1] == "no_requests"
    assert plan_ex(_traffic(requests), jx,
                   policy="fixed", batch_size=0)[1] == "batch_size"
    assert plan_ex(_traffic(requests), jx, policy="occupancy",
                   min_share=-1.0)[1] == "min_share_out_of_range"
    # legacy single-value shape still works
    assert plan(_traffic(requests), fast) is None


# ------------------------------------------------------------- property
@given(st.integers(0, 2 ** 16))
@settings(max_examples=8, deadline=None)
def test_random_traces_bit_identical(seed):
    """Random small arrival traces across the whole widened domain: the
    jitted program is bit-identical to the numpy client wherever
    ``plan_ex`` accepts."""
    import random
    rng = random.Random(seed)
    fast, jx = _chips(n_cores=rng.choice((1, 2, 3)),
                      design=rng.choice(ALL_DESIGNS),
                      bw_bytes_per_cycle=rng.choice((16.0, 32.0, 64.0)),
                      share_policy=rng.choice(("equal", "demand")))
    policy = rng.choice(("fixed",) + REACTIVE)
    batch_size = rng.choice((1, 2, 4)) if policy == "fixed" else 1
    requests = _trace(rng.randrange(1, 9), seed=seed % 1024,
                      mean_gap=rng.choice((0, 1, 3)))
    _assert_in_domain(requests, jx, policy=policy, batch_size=batch_size)
    _assert_identical(requests, fast, jx, policy=policy,
                      batch_size=batch_size)


@pytest.mark.parametrize("policy", ("fixed", "occupancy"))
def test_window_doubling_smoke(policy):
    """Deterministic pin of the window-independence property (runs even
    without hypothesis): doubling the sliding window moves no bits."""
    _, jx = _chips(n_cores=2)
    requests = _trace(8, seed=21, mean_gap=1)
    p = _assert_in_domain(requests, jx, policy=policy)
    fin0, adm0 = finish_admit_times(p)
    fin1, adm1 = finish_admit_times(dataclasses.replace(p, S=p.S * 2))
    assert (fin0 == fin1).all()
    assert (adm0 == adm1).all()


@given(st.integers(0, 2 ** 16), st.integers(1, 3))
@settings(max_examples=6, deadline=None)
def test_window_size_independence(seed, scale):
    """Chunk-boundary placement is invisible: the sliding settled-prefix
    window is sized by the span bound ``S``, and running the same plan
    with a window 2x/4x/8x larger must reproduce every finish and admit
    epoch bit-for-bit (the window only decides *where* settled epochs
    spill out of the carry, never their values)."""
    import random
    rng = random.Random(seed)
    policy = rng.choice(("fixed", "occupancy"))
    _, jx = _chips(n_cores=rng.choice((1, 2)))
    requests = _trace(rng.randrange(2, 8), seed=seed % 512,
                      mean_gap=rng.choice((0, 2)))
    p = _assert_in_domain(requests, jx, policy=policy)
    fin0, adm0 = finish_admit_times(p)
    wide = dataclasses.replace(p, S=p.S * 2 ** scale)
    fin1, adm1 = finish_admit_times(wide)
    assert (fin0 == fin1).all()
    assert (adm0 == adm1).all()
