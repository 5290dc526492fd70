"""Contention-aware serving batcher: latency/makespan vs. offered load.

Drives synthetic serving-request traces (prefill GEMM + decode micro-GEMMs
per request) through the online chip model under the three admission
policies of ``repro.serving.simbatch`` -- the blind fixed-batch baseline,
bandwidth-threshold admission, and the occupancy-aware policy -- across a
sweep of offered loads (mean inter-arrival gap in scheduling epochs), plus
the canonical skewed 4-core acceptance scenario.  Reported per cell: p50 /
p99 request latency (cycles), makespan, and MACs/cycle throughput, all on
the fast simulation backend (results are backend-independent; the parity
suite pins reference == fast).

Also: the whole-scenario ``vmap`` demo -- an arrival-rate sweep (same
request universe, arrival epochs rescaled per variant) settled as ONE
vmapped launch of the jitted whole-trace arbiter
(:func:`repro.multicore.jitarb.finish_times_many`), each variant's report
asserted bit-identical to a sequential numpy-client run.

Results go to ``benchmarks/results/BENCH_serving_batch.json`` -- uploaded
by CI next to the other benchmark artifacts.

    PYTHONPATH=src python benchmarks/serving_batch.py [--smoke]
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import time
from pathlib import Path

import common  # noqa: F401  -- puts <repo>/src on sys.path

from repro.multicore import ChipConfig, jitarb  # noqa: E402
from repro.obs import TelemetryConfig, write_trace  # noqa: E402
from repro.obs.attribution import BUCKETS  # noqa: E402
from repro.serving.simbatch import (POLICIES,  # noqa: E402
                                    report_from_finishes, run_batcher,
                                    skewed_trace, synthetic_trace)

from common import RESULTS, emit, write_bench  # type: ignore  # noqa: E402

#: offered-load sweep: mean inter-arrival gap in epochs (small = heavy)
LOADS = (1, 4, 16)
SMOKE_LOADS = (2, 8)
BW = 64.0           # binding enough on 4 RASA-WLBP cores that policy matters


def _cell(rep) -> dict:
    return {
        "makespan": rep.makespan,
        "p50_latency": rep.p50_latency,
        "p99_latency": rep.p99_latency,
        "mean_latency": rep.mean_latency,
        "throughput_macs_per_cycle": rep.throughput_macs_per_cycle,
        "admit_epochs": list(rep.admit_epochs),
    }


#: arrival-rate sweep factors: each variant compresses the base trace's
#: arrival epochs by this much (smaller = heavier offered load)
RATE_FACTORS = (1.0, 0.5, 0.25)


def rate_sweep_vmap(smoke: bool = False) -> dict:
    """The whole-serving-scenario ``vmap`` demo: an arrival-rate sweep of
    one request universe runs as ONE device launch.

    Every variant keeps the same request shapes and only rescales the
    arrival epochs, so :func:`repro.multicore.jitarb.plan_many` unifies
    the trace table and :func:`finish_times_many` settles all variants in
    a single vmapped XLA call.  Each variant's ``BatchReport`` must be
    bit-identical to a sequential numpy-client run (asserted) -- the
    sweep changes the launch shape, never the answer.
    """
    n_req = 24 if smoke else 64
    base = synthetic_trace(n_req, seed=3, mean_gap=4, d_model=128,
                           prompt_lens=(16, 32, 64), decode_steps=(1, 2),
                           decode_batch=8)
    chip_np = ChipConfig(n_cores=4, design="RASA-WLBP",
                         bw_bytes_per_cycle=32.0, backend="fast")
    chip_jit = dataclasses.replace(chip_np, backend="jax")
    variants = [[dataclasses.replace(r, arrival_epoch=int(r.arrival_epoch
                                                          * f))
                 for r in base] for f in RATE_FACTORS]

    plans = jitarb.plan_many([[(r.arrival_epoch, r.specs) for r in v]
                              for v in variants], chip_jit)
    assert plans is not None, "sweep unexpectedly outside the jitarb domain"
    t0 = time.perf_counter()
    outs = jitarb.finish_times_many(plans)
    t_vmap = time.perf_counter() - t0

    t0 = time.perf_counter()
    oracles = [run_batcher(v, chip_np, policy="fixed", batch_size=1)
               for v in variants]
    t_seq = time.perf_counter() - t0

    cells = {}
    for f, v, fin, oracle in zip(RATE_FACTORS, variants, outs, oracles):
        rep = report_from_finishes(v, chip_jit, fin)
        assert rep == oracle, \
            f"vmapped variant x{f} diverged from the sequential numpy " \
            f"client -- the sweep may only change the launch shape"
        cells[f"x{f}"] = {"makespan": rep.makespan,
                          "p50_latency": rep.p50_latency,
                          "p99_latency": rep.p99_latency}
    return {"n_requests": n_req, "factors": list(RATE_FACTORS),
            "seconds_vmap_launch": t_vmap, "seconds_numpy_seq": t_seq,
            "identical_reports": True, "cells": cells}


def run(smoke: bool = False) -> dict:
    n_req, d_model = (8, 256) if smoke else (16, 512)
    chip = ChipConfig(n_cores=4, design="RASA-WLBP",
                      bw_bytes_per_cycle=BW, backend="fast")
    table: dict = {"smoke": smoke, "chip": {
        "n_cores": chip.n_cores, "design": chip.design,
        "bw_bytes_per_cycle": chip.bw_bytes_per_cycle,
        "epoch_cycles": chip.epoch_cycles}, "load_sweep": {}, "skewed": {}}

    for gap in (SMOKE_LOADS if smoke else LOADS):
        trace = synthetic_trace(n_req, seed=0, mean_gap=gap,
                                d_model=d_model)
        for policy in POLICIES:
            rep = run_batcher(trace, chip, policy=policy)
            table["load_sweep"][f"gap{gap}_{policy}"] = _cell(rep)

    skew = skewed_trace(d_model=256, heavy_prompt=256, n_light=6) if smoke \
        else skewed_trace()
    tcfg = TelemetryConfig(enabled=True, stages=True)
    skew_reports = {}
    for policy in POLICIES:
        # telemetry on: the skewed scenario doubles as the acceptance run
        # for the Perfetto artifact + bucket-conservation property
        rep = run_batcher(skew, chip, policy=policy, telemetry=tcfg)
        skew_reports[policy] = rep
        att = rep.attribution
        occupied = sum(att.total(b) for b in BUCKETS)
        assert math.isclose(occupied, att.occupied_cycles,
                            rel_tol=1e-9, abs_tol=1e-6), \
            f"attribution buckets must sum to window x cores " \
            f"({occupied} != {att.occupied_cycles})"
        table["skewed"][policy] = {**_cell(rep),
                                   "attribution": att.fractions()}
    fixed = table["skewed"]["fixed"]["makespan"]
    occ = table["skewed"]["occupancy"]["makespan"]
    table["skewed"]["occupancy_vs_fixed_makespan"] = occ / fixed
    assert occ < fixed, "occupancy-aware admission must beat fixed-batch " \
                        "on the skewed trace"

    # Perfetto-loadable artifact of the occupancy run (CI uploads it)
    RESULTS.mkdir(parents=True, exist_ok=True)
    write_trace(skew_reports["occupancy"].telemetry,
                RESULTS / "serving_skewed.trace.json")

    table["rate_sweep_vmap"] = rate_sweep_vmap(smoke)

    write_bench("serving_batch", table, backend="fast")
    return table


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller trace (CI smoke run)")
    args = ap.parse_args(argv)
    t = run(smoke=args.smoke)
    print(f"# offered-load sweep (4 cores, RASA-WLBP, {BW:.0f} B/cyc)")
    print(f"{'cell':<22}{'makespan':>12}{'p50':>12}{'p99':>12}")
    for key, v in t["load_sweep"].items():
        print(f"{key:<22}{v['makespan']:>12.0f}{v['p50_latency']:>12.0f}"
              f"{v['p99_latency']:>12.0f}")
        emit(f"serving_{key}", 0.0,
             f"makespan={v['makespan']:.0f};p99={v['p99_latency']:.0f}")
    print("\n# skewed acceptance scenario (attribution: "
          + "/".join(BUCKETS) + ")")
    for policy in POLICIES:
        v = t["skewed"][policy]
        att = "/".join(f"{v['attribution'][b]:.0%}" for b in BUCKETS)
        print(f"{policy:<12} makespan={v['makespan']:>12.0f} "
              f"p50={v['p50_latency']:>10.0f} p99={v['p99_latency']:>10.0f} "
              f"{att}")
        emit(f"serving_skewed_{policy}", 0.0,
             f"makespan={v['makespan']:.0f}")
    ratio = t["skewed"]["occupancy_vs_fixed_makespan"]
    print(f"occupancy-aware makespan = {ratio:.3f}x fixed-batch "
          f"(lower is better; <1 required)")

    rs = t["rate_sweep_vmap"]
    print(f"\n# arrival-rate sweep as ONE vmapped launch "
          f"({rs['n_requests']} requests x {len(rs['factors'])} variants)")
    for key, v in rs["cells"].items():
        print(f"{key:<12} makespan={v['makespan']:>12.0f} "
              f"p50={v['p50_latency']:>10.0f} p99={v['p99_latency']:>10.0f}")
    print(f"one launch {rs['seconds_vmap_launch']:.2f}s (incl. one-off "
          f"compile; see online_scaling.py for at-scale timings) vs "
          f"sequential numpy {rs['seconds_numpy_seq']:.2f}s (identical "
          f"BatchReports: {rs['identical_reports']})")
    emit("serving_rate_sweep_vmap", rs["seconds_vmap_launch"] * 1e6,
         f"variants={len(rs['factors'])};n={rs['n_requests']}")


if __name__ == "__main__":
    main()
