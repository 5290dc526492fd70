#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this process finds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``perfbench/configs/<config>.json``, the
sizes as run), whose ``model_type`` names its architecture's adapter
(``perfbench/arch/<model_type>.py``: the program's model and the plain
reference of its layer equations), and a traffic mix
(``perfbench/traffic/<traffic>.json``, parameters of the one question
generator in ``questions.py``). A run:

1. refuses, before any question, a configuration with no adapter, a
   platform that is not a TPU or fewer chips than the cell asks for
   (exit 3, no result);
2. set-up: imports, the chip, programs from the persistent compilation
   cache in ``<checkout>/.jax_cache``, and one warm-up question drawn
   outside the window's list (``setup_s`` ends here);
3. the window, a closed loop: compile the question's layer with
   ``compile_workload`` and answer it with ``sweep_workload(...,
   backend="fast")``, one question after another until ``--seconds`` have
   passed, finishing the question in flight;
4. reads the device's peak memory, then holds every answered question
   against the plain reference (``check.py``);
5. prints each compared number beside its limit as the last lines of
   standard error, and one JSON result as the last line of standard
   output: the end-to-end metrics with ``--trace 0``, the per-layer
   metrics (each read by ``perfbench/metrics/<name>.py`` from the
   profiler trace of the window and jax's compile counter) with
   ``--trace 1``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import check  # noqa: E402
from perfbench.questions import questions, warmup_question  # noqa: E402

#: the platform every cell runs on; anything else is refused
PLATFORM = "tpu"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"


def _read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path | None = None) -> dict:
    """The cell's entry, configuration, architecture adapter, mix, design
    table and metrics.

    The adapter is ``perfbench/arch/<model_type>.py`` under ``root``; a
    configuration whose ``model_type`` has none raises
    ``FileNotFoundError`` naming the file looked for.
    """
    root = ROOT if root is None else root
    bench = _read_json(root / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _read_json(root / conf["file"])
    arch = root / "perfbench" / "arch" / f"{cfg['model_type']}.py"
    if not arch.is_file():
        raise FileNotFoundError(
            f"{conf['file']} has model_type {cfg['model_type']!r}, and "
            f"there is no adapter {arch}")
    return {
        "cell": cell,
        "config": cfg,
        "arch": _module(arch, f"perfbench_arch_{cfg['model_type']}"),
        "mix": _read_json(root / "perfbench" / "traffic"
                          / f"{cell['traffic']}.json"),
        "table": _read_json(root / "perfbench" / "designs.json"),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
    }


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _peak_rss() -> str:
    """This process's peak resident memory so far (Linux counts KiB)."""
    kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return f"host peak RSS {kib / 2**20:.2f} GiB"


def _metric_reader(name: str):
    return _module(BENCH_DIR / "metrics" / f"{name}.py",
                   f"perfbench_metric_{name}").read


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             devices: list) -> dict:
    """Set-up, window and check of one run; returns the result dict."""
    import jax
    import repro.core as core
    from repro.workload.compile import compile_workload

    from perfbench.clock import CompileClock

    mix, table = c["mix"], c["table"]
    model, opts = c["arch"].program(c["config"])
    fields = set(core.EngineConfig.__dataclass_fields__)

    def answer(q: dict, max_gemms: int | None = None):
        with jax.profiler.TraceAnnotation("question.build"):
            wl = compile_workload(model, batch=q["batch"], seq=q["seq"],
                                  phase=q["phase"], options=opts)
            specs = list(wl.specs)[:max_gemms]
            cfgs = [core.EngineConfig(**{k: v for k, v in d.items()
                                         if k in fields})
                    for d in q["designs"]]
        with jax.profiler.TraceAnnotation("question.sweep"):
            grid = core.sweep_workload(specs, cfgs, backend="fast")
        return specs, grid

    warm = warmup_question(mix, table, seed)
    wopts = mix.get("warmup", {})
    with CompileClock() as setup_clock:
        tw = time.perf_counter()
        if wopts.get("lower_all_gemms"):
            answer({**warm, "designs": warm["designs"][:1]})
        answer(warm, wopts.get("max_gemms"))
    _log(f"warm-up: {time.perf_counter() - tw:.3f} s, compile "
         f"{setup_clock.seconds:.3f} s, {setup_clock.compiles} compiles, "
         f"{setup_clock.cache_hits} cache hits, {_peak_rss()}")

    answered: list[dict] = []
    instrs = 0
    qs = questions(mix, table, seed)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # the Python tracer would record every Python call of the
        # lowering: traced prefill questions took 65% longer with it
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=popts)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                q = next(qs)
                specs, grid = answer(q)
                rows = [{name: {"cycles": r.cycles, "n_mm": r.n_mm,
                                "n_tl": r.n_tl, "n_ts": r.n_ts,
                                "wl_skips": r.wl_skips,
                                "utilization": r.utilization}
                         for name, r in row.items()} for row in grid]
                instrs += sum(a["n_mm"] + a["n_tl"] + a["n_ts"]
                              for row in rows for a in row.values())
                answered.append({**q, "results": rows,
                                 "gemms": [(s.name, s.M, s.K, s.N)
                                           for s in specs]})
                now = time.perf_counter()
                _log(f"question {len(answered)}: batch {q['batch']} seq "
                     f"{q['seq']}, {len(specs)} GEMMs x {len(q['designs'])} "
                     f"designs, ends at {now - t0:.3f} s, total "
                     f"{instrs} instructions")
                if now - t0 >= seconds:
                    break
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    _log(f"window: {t1 - t0:.3f} s, {_peak_rss()}")

    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    del grid
    gc.collect()

    tr = time.perf_counter()
    checks = check.compare(answered, c, seed)
    _log(f"reference: {time.perf_counter() - tr:.3f} s, {_peak_rss()}")
    failed = sum(any(len(row) < len(q["designs"]) for row in q["results"])
                 or len(q["results"]) < len(q["gemms"]) for q in answered)
    out = {"correct": check.passed(checks), "attempted": len(answered),
           "failed": failed}
    reduced = None
    if not trace:
        values = {"sweep_minstr_per_s": instrs / (t1 - t0) / 1e6,
                  "setup_s": t0 - T_START}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    else:
        from perfbench import xtrace
        tx = time.perf_counter()
        reduced = xtrace.reduce_events(xtrace.load(str(TRACE_DIR)))
        run = {"questions": len(answered), "trace": reduced,
               "compiles_in_window": clock.compiles}
        metrics = {}
        for m in c["per_layer"]:
            v = _metric_reader(m["name"])(run)
            if v is None:
                _log(f"metric {m['name']}: nothing to read in the trace")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        _log(f"trace read: {time.perf_counter() - tx:.3f} s, {_peak_rss()}")
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
    out["metrics"] = metrics
    out["device"] = device
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def emit(out: dict) -> None:
    """Each compared number beside its limit as the last lines of standard
    error; the result as the last line of standard output."""
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        c = load_cell(args.workload)
    except FileNotFoundError as e:
        _log(f"refused: {e}")
        return 3

    import jax
    devices = jax.devices()
    chips = c["cell"]["chips"]
    if devices[0].platform != PLATFORM or len(devices) < chips:
        _log(f"refused: cell {args.workload} needs {chips} {PLATFORM} "
             f"chip(s), found {len(devices)} {devices[0].platform} device(s)")
        return 3
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    emit(run_cell(c, args.seed, args.seconds, bool(args.trace),
                  devices[:chips]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
