"""Simulator-core throughput: reference loop vs trace-compiled backends.

Measures (1) single-stream instructions/second per backend, (2) end-to-end
wall time of the 8-design x multi-GEMM sweep (``repro.core.sweep_workload``)
on the reference backend vs the fast backend (cold = includes trace + XLA
compilation, warm = steady state), and (3) the 4-core epoch-arbitration
comparison from ``multicore_scaling`` on the reference vs fast chip backend.

Results go to ``benchmarks/results/BENCH_sim_throughput.json`` -- the perf
trajectory artifact CI uploads next to the multicore benchmark.

    PYTHONPATH=src python benchmarks/sim_throughput.py [--smoke]
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import common  # noqa: F401  -- puts <repo>/src on sys.path

from repro.core import (TABLE_I, get_design, simulate, sweep_workload,
                        PipelineSimulator)
from repro.core import fastsim, simulator, tiling
from repro.core import trace as trace_mod
from repro.core.tiling import ALG1_POLICY, lowered_stream
from repro.core.trace import gemm_trace
from repro.multicore import ChipConfig, simulate_chip

from common import emit, write_bench  # type: ignore

#: the multi-GEMM design-sweep workload (all DLRM + BERT layers of Table I;
#: the ResNet50 layers' ~2M-instruction streams are left out to keep the CI
#: smoke run bounded)
SWEEP_WORKLOAD = ("DLRM-1", "DLRM-2", "DLRM-3", "BERT-1", "BERT-2", "BERT-3")
SMOKE_WORKLOAD = ("DLRM-2", "BERT-1", "DLRM-1")

#: skewed scheduler workload for the multicore section (cf.
#: benchmarks/multicore_scaling.py)
MC_WORKLOAD = ("DLRM-2", "BERT-1", "DLRM-2", "BERT-1", "DLRM-2", "DLRM-2")
MC_BW = 32.0


def _clear_caches() -> None:
    simulator._simulate_cached.cache_clear()
    tiling._lowered_stream_cached.cache_clear()
    # dropping the trace cache also releases the per-trace MM analyses
    # (fastsim._MM_CACHE holds them under weak keys)
    trace_mod._compiled_trace_cached.cache_clear()


def bench_stream(design: str = "RASA-WLBP", spec_name: str = "BERT-1") -> dict:
    """Single-stream instructions/second per backend."""
    spec = TABLE_I[spec_name]
    cfg = get_design(design)
    stream = lowered_stream(spec, ALG1_POLICY)
    trace = gemm_trace(spec, ALG1_POLICY)
    n = len(stream)
    out = {"design": design, "workload": spec_name, "n_instrs": n}

    t0 = time.perf_counter()
    ref = PipelineSimulator(cfg).run(stream)
    out["reference_instrs_per_sec"] = n / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    fast = fastsim.run_trace_numpy(trace, cfg)
    out["numpy_instrs_per_sec"] = n / (time.perf_counter() - t0)
    assert fast.cycles == ref.cycles

    cfgs = [get_design(d) for d in
            ("BASE", "RASA-PIPE", "RASA-WLBP", "RASA-DB-WLS",
             "RASA-DM-PIPE", "RASA-DM-WLBP", "RASA-DMDB-WLS",
             "RASA-DB-WLBP")]
    fastsim.sweep_trace(trace, cfgs, backend="jax")    # compile
    t0 = time.perf_counter()
    res = fastsim.sweep_trace(trace, cfgs, backend="jax")
    dt = time.perf_counter() - t0
    # batched rate: per-design instructions retired per second
    out["jax_batch8_instrs_per_sec"] = n * len(cfgs) / dt
    assert abs(res[2].cycles - ref.cycles) <= 1e-6 * ref.cycles
    return out


def bench_sweep(workload: tuple[str, ...]) -> dict:
    """8-design x multi-GEMM sweep: reference vs fast, cold and warm."""
    specs = [TABLE_I[k] for k in workload]
    out = {"workload": list(workload), "n_designs": 8,
           "n_instrs": sum(len(lowered_stream(s, ALG1_POLICY))
                           for s in specs)}

    _clear_caches()
    t0 = time.perf_counter()
    ref = sweep_workload(specs, backend="reference")
    out["reference_s"] = time.perf_counter() - t0

    _clear_caches()          # cold really means cold: traces recompile too
    t0 = time.perf_counter()
    cold = sweep_workload(specs, backend="fast")
    out["fast_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = sweep_workload(specs, backend="fast")
    out["fast_warm_s"] = time.perf_counter() - t0

    for r, w in zip(ref, warm):
        for k in r:
            rel = abs(r[k].cycles - w[k].cycles) / max(1.0, r[k].cycles)
            assert rel <= 1e-6, (k, r[k].cycles, w[k].cycles)
    out["speedup_cold"] = out["reference_s"] / out["fast_cold_s"]
    out["speedup_warm"] = out["reference_s"] / out["fast_warm_s"]
    out["backend_resolved"] = fastsim.resolve_backend(
        "fast", out["n_instrs"] * 8)
    return out


def bench_multicore() -> dict:
    """Epoch-arbitration comparison wall time, reference vs fast backend."""
    specs = [TABLE_I[k] for k in MC_WORKLOAD]
    out = {"workload": list(MC_WORKLOAD), "n_cores": 4,
           "bw_bytes_per_cycle": MC_BW}
    reps = {}
    for backend in ("reference", "fast"):
        t0 = time.perf_counter()
        for arb in ("static", "epoch"):
            reps[backend, arb] = simulate_chip(
                specs, ChipConfig(n_cores=4, design="RASA-WLBP",
                                  bw_bytes_per_cycle=MC_BW, arbitration=arb,
                                  backend=backend),
                scheduler="lpt")
        out[f"{backend}_s"] = time.perf_counter() - t0
    for arb in ("static", "epoch"):
        ref, fast = reps["reference", arb], reps["fast", arb]
        rel = abs(ref.cycles - fast.cycles) / ref.cycles
        assert rel <= 1e-6, (arb, ref.cycles, fast.cycles)
        out[f"{arb}_cycles"] = fast.cycles
    out["epoch_arb_skipped"] = list(reps["fast", "epoch"].arb_skipped)
    out["speedup"] = out["reference_s"] / out["fast_s"]
    return out


def run(smoke: bool = False) -> dict:
    table = {
        "stream": bench_stream(),
        "sweep": bench_sweep(SMOKE_WORKLOAD if smoke else SWEEP_WORKLOAD),
        "multicore": bench_multicore(),
        "smoke": smoke,
    }
    write_bench("sim_throughput", table, backend="fast")
    return table


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller sweep workload (CI smoke run)")
    args = ap.parse_args(argv)
    t = run(smoke=args.smoke)

    s = t["stream"]
    print(f"# single stream ({s['design']} x {s['workload']}, "
          f"{s['n_instrs']} instrs)")
    for k in ("reference", "numpy", "jax_batch8"):
        key = f"{k}_instrs_per_sec"
        if key in s:
            print(f"{k:<12} {s[key]:>12.0f} instrs/s")
            emit(f"sim_throughput_{k}", 0.0, f"ips={s[key]:.0f}")

    w = t["sweep"]
    print(f"\n# 8-design x {len(w['workload'])}-GEMM sweep "
          f"({w['n_instrs']} instrs/design)")
    print(f"reference {w['reference_s']:.2f}s   fast cold "
          f"{w['fast_cold_s']:.2f}s ({w['speedup_cold']:.1f}x)   "
          f"fast warm {w['fast_warm_s']:.2f}s ({w['speedup_warm']:.1f}x)")
    emit("sim_throughput_sweep", 0.0,
         f"ref={w['reference_s']:.2f}s;warm={w['fast_warm_s']:.2f}s;"
         f"speedup={w['speedup_warm']:.1f}")

    m = t["multicore"]
    print(f"\n# 4-core epoch arbitration (x2 models, {MC_BW:.0f} B/cyc)")
    print(f"reference {m['reference_s']:.2f}s   fast {m['fast_s']:.2f}s "
          f"({m['speedup']:.1f}x)   skipped/round={m['epoch_arb_skipped']}")
    emit("sim_throughput_multicore", 0.0,
         f"ref={m['reference_s']:.2f}s;fast={m['fast_s']:.2f}s;"
         f"speedup={m['speedup']:.1f}")


if __name__ == "__main__":
    main()
