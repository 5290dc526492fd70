"""Online-arbiter scaling: the jitted whole-trace program vs. the numpy
client, plus the settled-prefix cache vs. rebuild-from-epoch-0.

Two comparisons, one trace family (light per-request shapes so arbitration
-- not engine simulation -- is what the wall clock measures):

**Jitted arbitration** (the headline, default 10k requests, ``-n`` scales
to 100k): the same open-arrival trace settles once through the numpy
incremental client (``backend="fast"``: the oracle) and once through the
whole-trace XLA program (``backend="jax"``, :mod:`repro.multicore.jitarb`
-- the entire boundary loop, share relaxation and token-bucket replay as
one ``lax.while_loop``).  The two ``BatchReport``s must be **bit-identical**
(asserted), and at full scale the jitted settle must be at least
``JIT_MIN_SPEEDUP`` (5x) faster than the numpy client (asserted on the
warm number: the one-off XLA compile is per trace-shape universe, not per
trace -- re-settling any same-shape trace, e.g. an arrival-rate sweep or
a load rescale, pays none of it).  The cold end-to-end time *including*
that compile is reported too and must still beat numpy
(``JIT_MIN_COLD_SPEEDUP``, asserted).  Measured at 10k requests: 113.1s
numpy vs. 15.3s cold / 11.0s warm = **7.4x cold / 10.3x warm**.

**Widened-domain points**: one small trace each through reactive
admission (``occupancy``/``bandwidth``/``predicted``), demand-weighted
shares, and a mixed BASE/RASA chip -- all settled by the same jitted
program (PR10's domain extensions) and asserted bit-identical to the
numpy client, with ``BatchReport.jit_gate`` confirming none of them fell
back.  A deliberate out-of-domain probe (``phase_aware``) checks the
structured plan-gate reason.  At ``-n 100000`` and beyond, the sliding
settled-prefix window's memory contract is asserted too: peak RSS stays
under ``JIT_MAX_RSS_MB`` regardless of trace horizon (the ``scale_100k``
block records the design point either way, so CI validates the contract
from the smoke run).

**Settled-prefix cache** (the earlier acceptance run, capped at 1000
requests): the numpy client with its settled-prefix cache and retired-span
pruning vs. the pre-refactor rebuild-from-epoch-0 mode
(``prefix_cache=False``) -- identical reports asserted, >= 5x at full
scale.  Measured at 1000 requests: 14.1s cached vs. 1548.9s baseline =
**109.5x**; the cap exists because the baseline is quadratic (~25 min at
1000 -- 10k would take days, which is rather the point).

Also emitted per run: arbiter settle/round counts, how the fast path
re-simulated (full replays vs. snapshot resumes), spans retired out of the
relaxation set, and the jitted kernel's relaxation-round / block-replay
counters.

Results go to ``benchmarks/results/BENCH_online_scaling.json`` -- uploaded
by CI next to the other benchmark artifacts and schema-checked by
``benchmarks/run.py --check-telemetry`` (CI runs ``--smoke``, which checks
the identities but not the speedup floors: compile time and the quadratic
term need full-scale traces to dominate).

``--resume`` additionally demonstrates checkpointed long-run simulation:
the trace is driven halfway, the chip is checkpointed
(:meth:`OnlineChip.snapshot`), round-tripped through ``pickle``, restored,
and driven to completion -- the restored run's makespan, share schedule
and retirement counts must be **bit-identical** to the uninterrupted run
(asserted; the ``resume_check`` block lands in the BENCH file).

    PYTHONPATH=src python benchmarks/online_scaling.py [--smoke] [-n N]
                                                       [--resume]
"""

from __future__ import annotations

import argparse
import dataclasses
import pickle
import resource
import time
from pathlib import Path

import common  # noqa: F401  -- puts <repo>/src on sys.path

from repro.core.fastsim import SNAP_STRIDE  # noqa: E402
from repro.multicore import ChipConfig, OnlineChip, jitarb  # noqa: E402
from repro.serving.simbatch import (_Batcher, run_batcher,  # noqa: E402
                                    synthetic_trace)

from common import emit, write_bench  # type: ignore  # noqa: E402

N_JIT_FULL = 10_000     # headline trace length (``-n`` scales to 100k+)
N_JIT_100K = 100_000    # chunked-window design point (``-n 100000``)
N_CACHE_FULL = 1000     # rebuild-from-0 baseline is quadratic: capped
N_SMOKE = 100
MIN_SPEEDUP = 5.0       # settled-prefix-cache floor, asserted at full scale
JIT_MIN_SPEEDUP = 5.0   # jitted-vs-numpy settle floor (warm)
JIT_MIN_COLD_SPEEDUP = 2.0  # incl. the one-off compile, jit must still win
#: peak-RSS ceiling of the 100k design point: the sliding settled-prefix
#: window keeps the carried state O(S), so memory must not scale with the
#: trace horizon (asserted whenever ``-n`` >= 100k)
JIT_MAX_RSS_MB = 8192.0

#: light per-request shapes: keeps both runs simulation-cheap so the
#: arbitration cost is what the comparison measures
TRACE_KW = dict(seed=0, mean_gap=2, d_model=128, prompt_lens=(16, 32, 64),
                decode_steps=(1, 2), decode_batch=8)
CHIP_KW = dict(n_cores=4, design="RASA-WLBP", bw_bytes_per_cycle=32.0,
               backend="fast")


def _run(requests, chip: ChipConfig, prefix_cache: bool):
    min_share = chip.bw_bytes_per_cycle / (2.0 * chip.n_cores)
    batcher = _Batcher(requests, chip, "occupancy", 4, min_share,
                       SNAP_STRIDE, 1, prefix_cache)
    t0 = time.perf_counter()
    rep = batcher.run()
    elapsed = time.perf_counter() - t0
    sim = batcher.sim
    return rep, elapsed, {**sim.stats, "n_retired": sim.n_retired}


def jit_check(n_requests: int, full_scale: bool) -> dict:
    """The headline comparison: one open-arrival trace, settled by the
    numpy incremental client and by the whole-trace XLA program; the
    reports must be bit-identical and (at full scale) the jitted path
    >= ``JIT_MIN_SPEEDUP`` faster."""
    requests = synthetic_trace(n_requests, **TRACE_KW)
    chip_np = ChipConfig(**CHIP_KW)
    chip_jit = dataclasses.replace(chip_np, backend="jax")

    t0 = time.perf_counter()
    rep_jit = run_batcher(requests, chip_jit, policy="fixed", batch_size=1)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep_warm = run_batcher(requests, chip_jit, policy="fixed", batch_size=1)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep_np = run_batcher(requests, chip_np, policy="fixed", batch_size=1)
    t_np = time.perf_counter() - t0

    assert rep_jit == rep_np and rep_warm == rep_np, \
        "jitted whole-trace arbitration must produce a bit-identical " \
        "BatchReport vs. the numpy oracle"
    assert rep_jit.jit_gate is None, \
        f"headline trace unexpectedly gated: {rep_jit.jit_gate}"

    # kernel-side counters (relaxation rounds, block replays) off a warm
    # re-settle -- negligible next to the timed runs above
    stats: dict = {}
    p = jitarb.plan([(r.arrival_epoch, r.specs) for r in requests],
                    chip_jit)
    assert p is not None, "trace unexpectedly outside the jitarb domain"
    jitarb.finish_times(p, stats)

    # a deliberately out-of-domain probe: the structured plan-gate reason
    # is what makes silent numpy fallbacks diagnosable, so its presence
    # is part of the benchmark contract (validated by run.py)
    _, gate_probe = jitarb.plan_ex(
        [(r.arrival_epoch, r.specs) for r in requests[:4]], chip_jit,
        policy="phase_aware")
    assert gate_probe == "admission_policy"

    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss \
        / 1024.0
    speedup = t_np / t_cold if t_cold else float("inf")
    speedup_warm = t_np / t_warm if t_warm else float("inf")
    if full_scale:
        assert speedup_warm >= JIT_MIN_SPEEDUP, \
            f"the jitted settle must be >= {JIT_MIN_SPEEDUP}x faster " \
            f"than the numpy path at {n_requests} requests " \
            f"(measured {speedup_warm:.1f}x warm)"
        assert speedup >= JIT_MIN_COLD_SPEEDUP, \
            f"even counting its one-off compile the jitted path must be " \
            f">= {JIT_MIN_COLD_SPEEDUP}x faster at {n_requests} requests " \
            f"(measured {speedup:.1f}x cold)"
    if n_requests >= N_JIT_100K:
        assert peak_rss_mb <= JIT_MAX_RSS_MB, \
            f"peak RSS {peak_rss_mb:.0f} MB exceeds the " \
            f"{JIT_MAX_RSS_MB:.0f} MB bound at {n_requests} requests -- " \
            f"the sliding settled-prefix window must keep memory O(S)"
    return {
        "n_requests": n_requests,
        "asserted": full_scale,
        "seconds_numpy": t_np,
        "seconds_jit_cold": t_cold,
        "seconds_jit_warm": t_warm,
        "speedup": speedup,
        "speedup_warm": speedup_warm,
        "identical_reports": True,
        "jit_gate": rep_jit.jit_gate,
        "gate_probe": gate_probe,
        "peak_rss_mb": peak_rss_mb,
        "kernel_rounds": stats.get("rounds"),
        "kernel_blocks": stats.get("blocks"),
        "makespan": rep_jit.makespan,
        "p50_latency": rep_jit.p50_latency,
        "p99_latency": rep_jit.p99_latency,
    }


#: widened-domain coverage points: each settles one small trace through
#: the numpy client and the jitted program, asserting bit-identity --
#: reactive admission, demand-weighted shares and a mixed BASE/RASA chip
#: all through the same kernel (PR10's domain extensions)
DOMAIN_POINTS = (
    ("occupancy", dict(policy="occupancy"), dict()),
    ("bandwidth", dict(policy="bandwidth"), dict()),
    ("predicted", dict(policy="predicted"), dict()),
    ("demand_shares", dict(policy="fixed", batch_size=1),
     dict(share_policy="demand")),
    ("hetero_mix", dict(policy="occupancy"),
     dict(n_cores=None, design=None, cores=("BASE", "RASA-WLBP",
                                            "RASA-WLBP", "RASA-WLBP"))),
)


def domain_check(n_requests: int) -> dict:
    """Settle one trace per widened-domain point through both paths;
    every report pair must be bit-identical and un-gated."""
    out: dict = {}
    for name, run_kw, chip_kw in DOMAIN_POINTS:
        kw = {**CHIP_KW, **chip_kw}
        chip_np = ChipConfig(**kw)
        chip_jit = dataclasses.replace(chip_np, backend="jax")
        requests = synthetic_trace(n_requests, **TRACE_KW)
        t0 = time.perf_counter()
        rep_jit = run_batcher(requests, chip_jit, **run_kw)
        t_jit = time.perf_counter() - t0
        t0 = time.perf_counter()
        rep_np = run_batcher(requests, chip_np, **run_kw)
        t_np = time.perf_counter() - t0
        assert rep_jit == rep_np, \
            f"domain point {name!r}: jitted BatchReport differs from " \
            f"the numpy oracle"
        assert rep_jit.jit_gate is None, \
            f"domain point {name!r} unexpectedly gated: {rep_jit.jit_gate}"
        out[name] = {
            "n_requests": n_requests,
            "seconds_numpy": t_np,
            "seconds_jit_cold": t_jit,
            "identical_reports": True,
            "jit_gate": rep_jit.jit_gate,
            "makespan": rep_jit.makespan,
        }
    return out


def _drive(sim: OnlineChip, requests, start: int = 0,
           upto_epoch: int | None = None) -> int:
    """Submit ``requests[start:]`` round-robin at their arrival epochs,
    stopping before the first arrival past ``upto_epoch``; returns the
    index of the first unsubmitted request."""
    n = sim.chip.n_cores
    i = start
    while i < len(requests):
        r = requests[i]
        if upto_epoch is not None and r.arrival_epoch > upto_epoch:
            return i
        if r.arrival_epoch > sim.epoch:
            sim.advance_to(r.arrival_epoch)
        sim.submit(i % n, r.specs)
        i += 1
    return i


def resume_check(n_requests: int) -> dict:
    """Checkpoint halfway, pickle-round-trip, restore, finish: the result
    must be bit-identical to the uninterrupted run."""
    requests = synthetic_trace(n_requests, **TRACE_KW)
    chip = ChipConfig(**CHIP_KW)
    half = requests[len(requests) // 2].arrival_epoch

    straight = OnlineChip(chip, snap_stride=SNAP_STRIDE)
    _drive(straight, requests)
    straight.drain()

    sim = OnlineChip(chip, snap_stride=SNAP_STRIDE)
    k = _drive(sim, requests, upto_epoch=half)
    sim.advance_to(half)
    blob = pickle.dumps(sim.snapshot())
    resumed = OnlineChip.restore(pickle.loads(blob))
    del sim                              # the checkpoint stands alone
    _drive(resumed, requests, start=k)
    resumed.drain()

    identical = (resumed.makespan == straight.makespan
                 and resumed.share_trace == straight.share_trace
                 and resumed.active_trace == straight.active_trace
                 and resumed.n_retired == straight.n_retired)
    assert identical, \
        "restoring a checkpoint changed the simulation -- snapshot/restore " \
        "must be bit-identical to never having checkpointed"
    return {
        "n_requests": n_requests,
        "checkpoint_epoch": half,
        "snapshot_pickle_bytes": len(blob),
        "makespan": straight.makespan,
        "identical": identical,
    }


def run(n_requests: int, smoke: bool = False,
        resume: bool = False) -> dict:
    jit = jit_check(n_requests, full_scale=n_requests >= N_JIT_FULL)
    domain = domain_check(min(n_requests, 500))

    # the 100k chunked-window design point: measured when this run is at
    # scale, otherwise recorded as the contract (floors + RSS bound) so
    # CI payload validation can gate on it from the smoke run
    measured_100k = n_requests >= N_JIT_100K
    scale_100k = {
        "n_requests": N_JIT_100K,
        "min_speedup_warm": JIT_MIN_SPEEDUP,
        "max_rss_mb": JIT_MAX_RSS_MB,
        "measured": measured_100k,
    }
    if measured_100k:
        scale_100k.update(speedup_warm=jit["speedup_warm"],
                          peak_rss_mb=jit["peak_rss_mb"])

    n_cache = min(n_requests, N_CACHE_FULL)
    requests = synthetic_trace(n_cache, **TRACE_KW)
    chip = ChipConfig(**CHIP_KW)
    rep_on, t_on, stats_on = _run(requests, chip, prefix_cache=True)
    rep_off, t_off, stats_off = _run(requests, chip, prefix_cache=False)

    assert rep_on == rep_off, \
        "prefix caching changed the BatchReport -- it may only change the " \
        "work, never the answer"
    speedup = t_off / t_on if t_on else float("inf")
    if n_cache >= N_CACHE_FULL:
        # the floor is only meaningful once the baseline's quadratic
        # arbiter term dominates; short custom -n runs just report
        assert speedup >= MIN_SPEEDUP, \
            f"prefix caching must be >= {MIN_SPEEDUP}x faster than the " \
            f"rebuild-from-epoch-0 baseline at {n_cache} requests " \
            f"(measured {speedup:.1f}x)"

    table = {
        "smoke": smoke,
        "n_requests": n_cache,
        "chip": {k: v for k, v in CHIP_KW.items()},
        "trace": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in TRACE_KW.items()},
        "jit": jit,
        "domain": domain,
        "scale_100k": scale_100k,
        "prefix_cache_on": {"seconds": t_on, **stats_on},
        "prefix_cache_off": {"seconds": t_off, **stats_off},
        "speedup": speedup,
        "identical_reports": True,
        "makespan": rep_on.makespan,
        "p50_latency": rep_on.p50_latency,
        "p99_latency": rep_on.p99_latency,
    }
    if resume:
        table["resume_check"] = resume_check(n_cache)
    write_bench("online_scaling", table, backend="fast")
    return table


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help=f"small trace ({N_SMOKE} requests, CI smoke run; "
                         f"checks the report identities, not the speedup "
                         f"floors)")
    ap.add_argument("-n", "--requests", type=int, default=None,
                    help=f"jitted-comparison trace length (default "
                         f"{N_JIT_FULL}, smoke {N_SMOKE}; the prefix-cache "
                         f"comparison is capped at {N_CACHE_FULL} -- its "
                         f"baseline is quadratic)")
    ap.add_argument("--resume", action="store_true",
                    help="also checkpoint the chip halfway, pickle "
                         "round-trip, restore and finish -- asserting the "
                         "result is bit-identical to the straight run")
    args = ap.parse_args(argv)
    n = args.requests or (N_SMOKE if args.smoke else N_JIT_FULL)
    t = run(n, smoke=args.smoke, resume=args.resume)

    j = t["jit"]
    print(f"# jitted whole-trace arbitration, {j['n_requests']} requests "
          f"({CHIP_KW['n_cores']} cores, {CHIP_KW['design']}, "
          f"{CHIP_KW['bw_bytes_per_cycle']:.0f} B/cyc)")
    print(f"{'path':<24}{'seconds':>10}")
    print(f"{'numpy client':<24}{j['seconds_numpy']:>10.2f}")
    print(f"{'jit (cold, w/ compile)':<24}{j['seconds_jit_cold']:>10.2f}")
    print(f"{'jit (warm)':<24}{j['seconds_jit_warm']:>10.2f}")
    print(f"speedup: {j['speedup']:.1f}x cold / {j['speedup_warm']:.1f}x "
          f"warm (identical BatchReport: {j['identical_reports']}; "
          f"{j['kernel_rounds']} relaxation rounds, "
          f"{j['kernel_blocks']} block replays; peak RSS "
          f"{j['peak_rss_mb']:.0f} MB)")

    print(f"\n# widened-domain parity points "
          f"({next(iter(t['domain'].values()))['n_requests']} requests)")
    print(f"{'point':<16}{'numpy s':>10}{'jit s':>10}{'identical':>11}")
    for name, row in t["domain"].items():
        print(f"{name:<16}{row['seconds_numpy']:>10.2f}"
              f"{row['seconds_jit_cold']:>10.2f}"
              f"{str(row['identical_reports']):>11}")

    on, off = t["prefix_cache_on"], t["prefix_cache_off"]
    print(f"\n# settled-prefix cache, {t['n_requests']} requests")
    print(f"{'mode':<24}{'seconds':>10}{'settles':>9}{'rounds':>8}"
          f"{'resumed':>9}{'retired':>9}")
    for name, row in (("prefix cache ON", on), ("rebuild from 0", off)):
        print(f"{name:<24}{row['seconds']:>10.2f}{row['settles']:>9}"
              f"{row['rounds']:>8}{row['sims_resumed']:>9}"
              f"{row['n_retired']:>9}")
    print(f"speedup: {t['speedup']:.1f}x (identical BatchReport: "
          f"{t['identical_reports']})")
    if "resume_check" in t:
        rc = t["resume_check"]
        print(f"resume: checkpoint @ epoch {rc['checkpoint_epoch']} "
              f"({rc['snapshot_pickle_bytes']} pickled bytes), restored "
              f"run bit-identical: {rc['identical']}")
    emit("online_scaling_jit", j["seconds_jit_cold"] * 1e6,
         f"speedup={j['speedup']:.1f};n={j['n_requests']}")
    emit("online_scaling_prefix_cache", on["seconds"] * 1e6,
         f"speedup={t['speedup']:.1f};n={t['n_requests']}")


if __name__ == "__main__":
    main()
