#!/usr/bin/env python3
"""Smoke run of the system's main path on one TPU chip.

    python3 chip_smoke.py

Runs four phases in this one process, each against its reference:

``serve``
    A ``qwen3-1.7b`` request trace at its published widths (one whole
    layer, the dense model's full period) served by ``run_batcher`` on a
    4-core chip whose bandwidth budget binds, through the jitted online
    settle (``multicore/jitarb.py``).  Passes when the jitted lane served
    it (``jit_gate`` is None) and its ``BatchReport`` equals the numpy
    client's exactly.
``sweep``
    ``sweep_workload`` of that layer's prefill GEMMs over all eight
    designs on the jitted timing scan (``core/fastsim.py``) against the
    numpy backend, cycles within 1e-6 relative.
``kernels``
    The RASA Pallas GEMM under ``base``, ``wlbp`` and ``wls`` at that
    layer's GEMM shapes, compiled for the chip (``interpret=False``),
    against ``kernels/ref.py``; the compiled program must hold a
    ``tpu_custom_call``.
``lm``
    The model stack's server (``ServeSession.generate``, as
    ``repro.launch.serve`` drives it) on the full ``qwen3-1.7b`` with
    random weights, ``pallas_rasa`` engine against the ``xla`` engine.

Every phase line gives the platform, device kind, compile seconds (set-up)
and run seconds, each timed up to ``block_until_ready``.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.  Without a TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import DESIGNS, sweep_workload  # noqa: E402
from repro.kernels import SCHEDULES, rasa_matmul  # noqa: E402
from repro.kernels.ref import ref_matmul  # noqa: E402
from repro.multicore import ChipConfig, jitarb  # noqa: E402
from repro.serving.simbatch import model_trace, run_batcher  # noqa: E402
from repro.workload.compile import CompileOptions, compile_workload  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0
#: one whole layer at published widths: a dense model's full period
LAYER = CompileOptions(max_layers=1)
#: the prompt length whose prefill GEMMs the sweep and kernel phases run
#: (the longest prompt ``model_trace`` draws)
PREFILL_SEQ = 128
#: requests the serve phase draws.  Widths are never cut, the trace length
#: is: on a v5e chip the jitted settle of 2 such requests runs about 150 s
#: (87k simulated blocks of emulated float64), and 8 requests cost the
#: numpy oracle alone about 8x what 2 do, past the run's time limit.
SERVE_REQUESTS = 2
SERVE_REQUESTS_WANTED = 8
#: the sweep's bound, the repo's backend-parity bound
SWEEP_REL = 1e-6
#: Pallas GEMM vs ``ref_matmul``: both accumulate exact bf16 products in
#: float32 and differ only in summation order, so a bf16 unit roundoff
#: (2**-8) of the output's largest magnitude is a loose bound
KERNEL_REL = 2.0 ** -8

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def compile_clock():
    """Seconds jax spent tracing, lowering and compiling inside the block
    (the union of those spans: nested traces count once), the programs it
    compiled, and how many came from the persistent compilation cache."""
    spans: list[tuple[float, float]] = []
    c = {"seconds": 0.0, "compiles": 0, "cache_hits": 0}

    def on_span(event, start, end, **_):
        if event in _COMPILE_EVENTS:
            spans.append((start, end))
            c["compiles"] += event == _COMPILE_EVENTS[-1]

    def on_event(event, **_):
        c["cache_hits"] += event == "/jax/compilation_cache/cache_hits"

    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        jax.monitoring.unregister_event_listener(on_event)
        reach = float("-inf")
        for start, end in sorted(spans):
            c["seconds"] += max(0.0, end - max(start, reach))
            reach = max(reach, end)


def timed(fn):
    """``(fn(), timing)``: wall seconds up to ``block_until_ready`` split
    into ``compile_s`` and ``run_s`` (the rest, host work included)."""
    with compile_clock() as c:
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
    return out, {"compile_s": c["seconds"], "run_s": wall - c["seconds"],
                 "compiles": c["compiles"], "cache_hits": c["cache_hits"]}


def _merge(*timings: dict) -> dict:
    return {k: sum(t[k] for t in timings) for k in timings[0]}


# ------------------------------------------------------------------ phases

def phase_serve(arch=ARCH, *, n_requests: int = SERVE_REQUESTS,
                options: CompileOptions = LAYER, n_cores: int = 4,
                budget: float = 64.0, seed: int = SEED) -> dict:
    """``run_batcher`` on the jitted settle vs the numpy client."""
    t0 = time.perf_counter()
    requests = model_trace(arch, n_requests, seed=seed, options=options)
    trace_s = time.perf_counter() - t0
    chip = ChipConfig(n_cores=n_cores, bw_bytes_per_cycle=budget,
                      backend="jax")
    rep, t_jax = timed(lambda: run_batcher(requests, chip,
                                           policy="occupancy"))
    # warm rerun of the same program: its relaxation rounds, and the
    # device time without compilation
    stats: dict = {}
    plan, _ = jitarb.plan_ex([(r.arrival_epoch, r.specs) for r in requests],
                             chip, policy="occupancy",
                             min_share=budget / (2.0 * n_cores))
    fins, t_warm = timed(lambda: jitarb.finish_admit_times(plan, stats)[0]
                         if plan is not None else None)
    ref, t_ref = timed(lambda: run_batcher(
        requests, dataclasses.replace(chip, backend="numpy"),
        policy="occupancy"))
    warm_same = fins is not None and \
        tuple(float(f) for f in fins) == rep.finish_times
    return {
        "ok": rep.jit_gate is None and rep == ref and warm_same,
        "n_requests": n_requests,
        "cut_from_requests": SERVE_REQUESTS_WANTED,
        "gemms": sum(len(r.specs) for r in requests),
        "jit_gate": rep.jit_gate, "equal_to_numpy": rep == ref,
        "warm_rerun_equal": warm_same,
        "makespan": rep.makespan, "p99_latency": rep.p99_latency,
        "arb_rounds": stats.get("rounds"), "sim_blocks": stats.get("blocks"),
        "trace_s": trace_s, **t_jax, "warm_run_s": t_warm["run_s"],
        "warm_compiles": t_warm["compiles"],
        "ref_s": t_ref["run_s"] + t_ref["compile_s"],
    }


def phase_sweep(arch=ARCH, *, seq: int = PREFILL_SEQ,
                options: CompileOptions = LAYER) -> dict:
    """All eight designs over one layer's prefill GEMMs: jax vs numpy."""
    specs = list(compile_workload(arch, batch=1, seq=seq, phase="prefill",
                                  options=options).specs)
    designs = list(DESIGNS)
    got, t_jax = timed(lambda: sweep_workload(specs, designs,
                                              backend="jax"))
    ref, t_ref = timed(lambda: sweep_workload(specs, designs,
                                              backend="numpy"))
    worst = max(abs(g[d].cycles - r[d].cycles) / r[d].cycles
                for g, r in zip(got, ref) for d in designs)
    skips_equal = all(g[d].wl_skips == r[d].wl_skips
                      for g, r in zip(got, ref) for d in designs)
    return {"ok": worst <= SWEEP_REL and skips_equal,
            "gemms": len(specs), "designs": len(designs),
            "max_rel_cycles_diff": worst, "bound": SWEEP_REL,
            "wl_skips_equal": skips_equal,
            "bit_identical": worst == 0.0, **t_jax,
            "ref_s": t_ref["run_s"] + t_ref["compile_s"]}


def layer_gemm_shapes(arch=ARCH, *, seq: int = PREFILL_SEQ,
                      options: CompileOptions = LAYER
                      ) -> list[tuple[int, int, int]]:
    """Distinct (M, K, N) of one layer's prefill GEMMs."""
    specs = compile_workload(arch, batch=1, seq=seq, phase="prefill",
                             options=options).specs
    return sorted({(s.M, s.K, s.N) for s in specs})


def phase_kernels(shapes, *, interpret: bool, seed: int = SEED) -> dict:
    """``rasa_matmul`` per schedule and shape against ``ref_matmul``."""
    rng = np.random.default_rng(seed)
    worst, custom, timings, n = 0.0, True, [], 0
    for m, k, n_ in shapes:
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        b = jnp.asarray(rng.standard_normal((k, n_)), jnp.bfloat16)
        want = np.asarray(ref_matmul(a, b))
        scale = float(np.abs(want).max())
        for schedule in SCHEDULES:
            fn = jax.jit(functools.partial(rasa_matmul, schedule=schedule,
                                           interpret=interpret))
            compiled, t_c = timed(lambda: fn.lower(a, b).compile())
            if not interpret:
                custom &= "tpu_custom_call" in compiled.as_text()
            got, t_r = timed(lambda: compiled(a, b))
            worst = max(worst, float(np.abs(np.asarray(got) - want).max())
                        / scale)
            timings += [t_c, t_r]
            n += 1
    return {"ok": worst <= KERNEL_REL and custom, "calls": n,
            "shapes": [list(s) for s in shapes],
            "schedules": list(SCHEDULES), "max_rel_err": worst,
            "bound": KERNEL_REL,
            "tpu_custom_call": custom if not interpret else "interpret",
            **_merge(*timings)}


#: pallas_rasa vs xla logits, relative to the largest |logit|.  Both
#: engines take exact bf16 products into float32 sums and round every
#: GEMM output to bf16; they differ only in summation order, so an output
#: moves by at most one bf16 ulp (2**-8 relative) where the orders round
#: apart, and such flips compound through the layers.  2**-5 leaves room
#: for that growth while a wrong kernel (a dropped k-chunk, a misplaced
#: tile) moves logits by O(1) of their scale.
LM_REL = 2.0 ** -5


def phase_lm(arch=ARCH, *, smoke: bool = False, batch: int = 2,
             prompt_len: int = 128, new_tokens: int = 16,
             seed: int = SEED) -> dict:
    """``ServeSession`` on the ``pallas_rasa`` engine vs the ``xla`` one."""
    from repro.configs import get_config
    from repro.distributed.sharding import mesh_context
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.serving import ServeSession

    cfg = get_config(arch, smoke=smoke)
    mesh = make_host_mesh(max_devices=1)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.model.vocab,
                                       (batch, prompt_len)), jnp.int32)
    out, params = {}, None
    with mesh_context(mesh, cfg.parallel):
        for kind in ("xla", "pallas_rasa"):
            api = build_model(dataclasses.replace(
                cfg, engine=dataclasses.replace(cfg.engine, kind=kind)))
            if params is None:
                params, t_init = timed(lambda: api.init(jax.random.key(seed)))
            session = ServeSession(api, params,
                                   max_seq=prompt_len + new_tokens + 8)
            toks, t_gen = timed(lambda: session.generate(prompts,
                                                         new_tokens))
            (logits, _), t_pre = timed(lambda: session.prefill(prompts))
            out[kind] = (np.asarray(toks),
                         np.asarray(logits, np.float32), t_gen, t_pre)
    tok_x, lg_x, t_gen_x, _ = out["xla"]
    tok_p, lg_p, t_gen_p, t_pre_p = out["pallas_rasa"]
    rel = float(np.abs(lg_p - lg_x).max() / np.abs(lg_x).max())
    in_vocab = bool(((tok_p >= 0) & (tok_p < cfg.model.vocab)).all())
    finite = bool(np.isfinite(lg_p).all())
    return {"ok": rel <= LM_REL and in_vocab and finite
            and tok_p.shape == (batch, new_tokens),
            "params_b": sum(x.size for x in jax.tree.leaves(params)) / 1e9,
            "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "max_rel_logit_diff": rel, "bound": LM_REL,
            "first_token_agree": float((tok_p[:, 0] == tok_x[:, 0]).mean()),
            "token_agree": float((tok_p == tok_x).mean()),
            "init_s": t_init["run_s"] + t_init["compile_s"],
            **t_gen_p, "prefill_run_s": t_pre_p["run_s"],
            "xla_compile_s": t_gen_x["compile_s"],
            "xla_run_s": t_gen_x["run_s"]}


# -------------------------------------------------------------------- main

def _line(name: str, dev, res: dict) -> str:
    fields = " ".join(f"{k}={v}" for k, v in res.items() if k != "ok")
    return (f"[{name}] platform={dev.platform} kind={dev.device_kind!r} "
            f"ok={res['ok']} {fields}")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    print(f"[setup] compile_cache={use_compile_cache(ROOT)} "
          f"devices={len(jax.devices())}", flush=True)
    phases = (("serve", phase_serve),
              ("sweep", phase_sweep),
              ("kernels", lambda: phase_kernels(layer_gemm_shapes(),
                                                interpret=False)),
              ("lm", phase_lm))
    ok = True
    with jax.default_device(dev):
        for name, fn in phases:
            try:
                res = fn()
            except Exception as e:  # report the phase, run the rest
                traceback.print_exc()
                res = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            ok &= bool(res["ok"])
            print(_line(name, dev, res), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
