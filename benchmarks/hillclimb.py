"""Perf hillclimb harness, two search modes.

Roofline mode (model-config variants, XLA-compiled terms):

    PYTHONPATH=src python benchmarks/hillclimb.py --arch grok-1-314b \
        --shape train_4k --variant fused_gate_up --variant remat_dots

Each variant is a named config transform; the harness compiles the full
cell (memory proof) + unrolled d0/d_unit (accurate flops/bytes/collectives)
and prints the three terms next to the baseline.  Results go to
benchmarks/results/hillclimb/<cell>__<variant>.json.

Engine design-search mode (matrix-engine configs, cycle simulator):

    PYTHONPATH=src python benchmarks/hillclimb.py --design-search \
        --workload bert --steps 20

Hillclimbs the RASA engine design space (array shape under the paper's
equal-multiplier constraint, control optimizations, LSQ parameters,
register policy) to minimize simulated cycles on a Table-I workload.
Every step evaluates the whole neighborhood in one batched fast-backend
design sweep (``repro.core.sweep_workload``), and perturbed frozen
``EngineConfig``s hit ``_simulate_cached`` instead of re-simulating.
Results go to benchmarks/results/hillclimb/design_search__<workload>.json.
"""

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path

import common  # noqa: F401  -- puts <repo>/src on sys.path

RESULTS = Path(__file__).resolve().parent / "results" / "hillclimb"
DRYRUN = Path(__file__).resolve().parent / "results" / "dryrun"


# ---------------------------------------------------------------- variants

def v_baseline(cfg):
    return cfg


def v_fused_gate_up(cfg):
    return dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, fuse_gate_up=True))


def v_remat_dots(cfg):
    return dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="dots"))


def v_serve_tp(cfg):
    return dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel,
                                          serve_param_sharding="tp"))


def v_microbatch4(cfg):
    return dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, microbatches=4))


def v_no_sp(cfg):
    # drop sequence parallelism of the residual stream: fewer per-layer
    # all-gathers at the cost of bigger carries (memory <-> collective)
    return cfg  # marker; applied via env knob below


def v_cap1(cfg):
    m = cfg.model
    return dataclasses.replace(
        cfg, model=dataclasses.replace(
            m, moe=dataclasses.replace(m.moe, capacity_factor=1.0)))


def v_groups64(cfg):
    m = cfg.model
    return dataclasses.replace(
        cfg, model=dataclasses.replace(
            m, moe=dataclasses.replace(m.moe, dispatch_groups=64)))


def v_ssd_chunk128(cfg):
    import dataclasses as dc
    m = cfg.model
    return dc.replace(cfg, model=dc.replace(
        m, ssm=dc.replace(m.ssm, chunk=128)))


def v_ssd_chunk64(cfg):
    import dataclasses as dc
    m = cfg.model
    return dc.replace(cfg, model=dc.replace(
        m, ssm=dc.replace(m.ssm, chunk=64)))


def v_opt_bf16(cfg):
    return dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel,
                                          opt_state_dtype="bfloat16"))


VARIANTS = {
    "baseline": v_baseline,
    "fused_gate_up": v_fused_gate_up,
    "remat_dots": v_remat_dots,
    "serve_tp": v_serve_tp,
    "microbatch4": v_microbatch4,
    "moe_cap1": v_cap1,
    "moe_groups64": v_groups64,
    "ssd_chunk128": v_ssd_chunk128,
    "ssd_chunk64": v_ssd_chunk64,
    "opt_bf16": v_opt_bf16,
}


def measure(arch: str, shape: str, variant: str, full: bool = True) -> dict:
    """Compile the variant cell + reduced-depth artifacts; return terms."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax  # noqa: F401  (mesh construction below needs devices)
    from repro.config import SHAPES
    from repro.configs import get_config
    from repro.distributed.sharding import mesh_context
    from repro.launch.dryrun import build_step, parse_collectives
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import (DRYRUN_DEVICE_KIND, analyze_cell,
                                         peaks_for)

    transform = VARIANTS[variant]
    seq_len, batch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=False)

    def compile_cfg(cfg):
        with mesh_context(mesh, cfg.parallel) as ctx:
            fn, args, sh, don = build_step(cfg, kind, seq_len, batch, ctx)
            c = jax.jit(fn, in_shardings=sh,
                        donate_argnums=don).lower(*args).compile()
            mem = c.memory_analysis()
            cost = c.cost_analysis()
            colls = parse_collectives(c.as_text())
        return {
            "memory": {"peak_bytes_per_device":
                       mem.argument_size_in_bytes + mem.output_size_in_bytes
                       + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
                       "temp_bytes_per_device": mem.temp_size_in_bytes},
            "cost_per_device": {"flops": cost.get("flops", 0.0),
                                "bytes_accessed": cost.get("bytes accessed", 0.0)},
            "collectives_per_device_bytes": colls,
        }

    base_cfg = get_config(arch)
    cfg = transform(base_cfg)
    unit = (cfg.model.hybrid.attn_every
            if cfg.model.family == "hybrid" else 1)

    def depth_cfg(c, depth):
        return dataclasses.replace(
            c,
            model=dataclasses.replace(c.model, n_layers=depth),
            parallel=dataclasses.replace(c.parallel, scan_layers=False),
            engine=dataclasses.replace(c.engine, attn_q_chunk=seq_len,
                                       attn_kv_chunk=seq_len,
                                       ce_chunk=seq_len, unroll_ssd=True))

    out = {"arch": arch, "shape": shape, "variant": variant,
           "devices": 256, "unit_layers": unit,
           "total_layers": cfg.model.n_layers}
    t0 = time.time()
    if full:
        out.update(compile_cfg(cfg))
    d0 = compile_cfg(depth_cfg(cfg, 0))
    du = compile_cfg(depth_cfg(cfg, unit))
    out["elapsed_s"] = round(time.time() - t0, 1)

    cell = {**out, "cost_per_device": out.get(
        "cost_per_device", d0["cost_per_device"]),
        "memory": out.get("memory", d0["memory"]),
        "collectives_per_device_bytes": out.get(
            "collectives_per_device_bytes", {})}
    d0f = {"cost_per_device": d0["cost_per_device"],
           "collectives_per_device_bytes": d0["collectives_per_device_bytes"]}
    duf = {"cost_per_device": du["cost_per_device"],
           "collectives_per_device_bytes": du["collectives_per_device_bytes"]}
    r = analyze_cell(cell, peaks_for(DRYRUN_DEVICE_KIND), d0=d0f, du=duf)
    out["roofline"] = {
        "compute_s": r.compute_s, "memory_s": r.memory_s,
        "collective_s": r.collective_s, "dominant": r.dominant,
        "step_time_s": r.step_time_s, "mfu": r.mfu,
        "useful_flops_ratio": r.useful_flops_ratio,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{arch}__{shape}__{variant}.json").write_text(
        json.dumps(out, indent=2))
    return out


# ------------------------------------------- engine design-space hillclimb

#: Table-I workloads the engine search can optimize for
SEARCH_WORKLOADS = {
    "bert": ("BERT-1", "BERT-2", "BERT-3"),
    "dlrm": ("DLRM-1", "DLRM-2", "DLRM-3"),
    "mixed": ("DLRM-2", "BERT-1", "DLRM-3", "BERT-3"),
}

#: equal-multiplier constraint (paper §V: every array has 512 multipliers)
N_MULTIPLIERS = 512


def _engine_candidates(state):
    """Single-knob neighbors of (engine kwargs, policy) under constraints."""
    import repro.core.tiling as tiling
    kw, policy = state
    POLICIES = (
        tiling.RegPolicy(mc=2, nc=2, a_regs=2, b_regs=2),
        tiling.RegPolicy(mc=4, nc=1, a_regs=2, b_regs=1),
        tiling.RegPolicy(mc=5, nc=1, a_regs=2, b_regs=1),
        tiling.RegPolicy(mc=1, nc=4, a_regs=1, b_regs=2),
        tiling.RegPolicy(mc=3, nc=1, a_regs=2, b_regs=2),
    )
    out = []
    for rows in (8, 16, 32, 64):
        for macs in (1, 2):
            cols = N_MULTIPLIERS // (rows * macs)
            if rows * macs * cols != N_MULTIPLIERS or cols < 4 or cols > 64:
                continue
            if (rows, macs) != (kw["rows"], kw["macs_per_pe"]):
                out.append(({**kw, "rows": rows, "cols": cols,
                             "macs_per_pe": macs}, policy))
    for flags in ((False, False, False, False), (True, False, False, False),
                  (True, True, False, False), (True, True, True, True),
                  (True, False, True, True), (True, True, False, True)):
        pipe, wlbp, wls, db = flags
        cand = {**kw, "pipe": pipe, "wlbp": wlbp, "wls": wls,
                "double_buffer": db}
        if cand != kw:
            out.append((cand, policy))
    for lat in (2, 5, 10, 20):
        if lat != kw["load_latency"]:
            out.append(({**kw, "load_latency": lat}, policy))
    for ports in (1, 2, 4):
        if ports != kw["load_ports"]:
            out.append(({**kw, "load_ports": ports}, policy))
    for pol in POLICIES:
        if pol != policy:
            out.append((kw, pol))
    return out


def design_search(workload: str = "bert", steps: int = 20,
                  backend: str = "fast") -> dict:
    """Greedy hillclimb over EngineConfig x RegPolicy on simulated cycles."""
    from repro.core import DESIGNS, TABLE_I, EngineConfig, get_design
    from repro.core import sweep_workload
    from repro.core.simulator import _simulate_cached
    from repro.core.tiling import ALG1_POLICY
    from repro.obs.attribution import simreport_attribution

    specs = [TABLE_I[k] for k in SEARCH_WORKLOADS[workload]]
    counter = [0]

    def attribution(policy, cycles) -> dict:
        """Unthrottled {compute, fill_drain, ...} split of one candidate --
        the 'why does this design win' column of the search log."""
        return simreport_attribution(specs, policy, cycles).fractions()

    def to_cfg(kw) -> EngineConfig:
        counter[0] += 1
        return EngineConfig(name=f"probe-{counter[0]}", **kw)

    seen: dict = {}

    def evaluate(states):
        """Batched cost of unseen states (total cycles over the workload)."""
        todo = [s for s in states
                if (_key(s)) not in seen]
        by_policy: dict = {}
        for s in todo:
            by_policy.setdefault(s[1], []).append(s)
        for policy, group in by_policy.items():
            cfgs = [to_cfg(kw) for kw, _ in group]
            rows = sweep_workload(specs, cfgs, policy, backend=backend)
            for s, cfg in zip(group, cfgs):
                seen[_key(s)] = sum(row[cfg.name].cycles for row in rows)
        return [seen[_key(s)] for s in states]

    def _key(state):
        kw, policy = state
        return (tuple(sorted(kw.items())), policy)

    start_cfg = get_design("RASA-DMDB-WLS")
    start = ({f.name: getattr(start_cfg, f.name)
              for f in dataclasses.fields(start_cfg) if f.name != "name"},
             ALG1_POLICY)
    cur, (cur_cost,) = start, evaluate([start])
    path = [{"step": 0, "engine": dict(cur[0]),
             "policy": dataclasses.asdict(cur[1]), "cycles": cur_cost,
             "attribution": attribution(cur[1], cur_cost)}]
    t0 = time.time()
    probes = 1
    for step in range(1, steps + 1):
        neigh = _engine_candidates(cur)
        probes += sum(1 for s in neigh if _key(s) not in seen)
        costs = evaluate(neigh)
        best_i = min(range(len(neigh)), key=lambda i: costs[i])
        if costs[best_i] >= cur_cost:
            break
        cur, cur_cost = neigh[best_i], costs[best_i]
        path.append({"step": step, "engine": dict(cur[0]),
                     "policy": dataclasses.asdict(cur[1]),
                     "cycles": cur_cost,
                     "attribution": attribution(cur[1], cur_cost)})
    elapsed = time.time() - t0

    # named baselines (exercises the EngineConfig-keyed _simulate_cached)
    baselines = {}
    for name in DESIGNS:
        cfg = get_design(name)
        baselines[name] = sum(
            _simulate_cached(s, cfg, ALG1_POLICY, backend).cycles
            for s in specs)
    out = {"workload": workload, "specs": [s.name for s in specs],
           "backend": backend, "probes": probes, "elapsed_s": elapsed,
           "path": path, "best_cycles": cur_cost,
           "named_baselines": baselines,
           "speedup_vs_best_named": min(baselines.values()) / cur_cost}
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"design_search__{workload}.json").write_text(
        json.dumps(out, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--design-search", action="store_true",
                    help="hillclimb the RASA engine design space instead of "
                         "the model-config roofline")
    ap.add_argument("--workload", default="bert",
                    choices=sorted(SEARCH_WORKLOADS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--backend", default="fast",
                    choices=("reference", "fast", "numpy", "jax"))
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", action="append", default=[])
    ap.add_argument("--skip-full", action="store_true",
                    help="skip the full-depth compile (terms only)")
    args = ap.parse_args()

    if args.design_search:
        r = design_search(args.workload, args.steps, args.backend)
        base = min(r["named_baselines"].items(), key=lambda kv: kv[1])
        print(f"design search [{args.workload}] {r['probes']} probes in "
              f"{r['elapsed_s']:.1f}s ({len(r['path']) - 1} accepted moves)")
        for p in r["path"]:
            e = p["engine"]
            a = p["attribution"]
            print(f"  step {p['step']:>2}  {p['cycles']:>12.0f} cyc  "
                  f"{e['rows']}x{e['cols']}x{e['macs_per_pe']} "
                  f"pipe={e['pipe']} wlbp={e['wlbp']} wls={e['wls']} "
                  f"lat={e['load_latency']} ports={e['load_ports']} "
                  f"policy={p['policy']['mc']}x{p['policy']['nc']}  "
                  f"compute={a['compute']:.0%} "
                  f"fill/drain={a['fill_drain']:.0%}")
        print(f"best {r['best_cycles']:.0f} cyc vs best named "
              f"{base[0]} {base[1]:.0f} cyc "
              f"({r['speedup_vs_best_named']:.2f}x)")
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required without --design-search")
    for v in (args.variant or ["baseline"]):
        r = measure(args.arch, args.shape, v, full=not args.skip_full)
        rf = r["roofline"]
        mem = r.get("memory", {}).get("peak_bytes_per_device", 0) / 2**30
        print(f"{args.arch} x {args.shape} [{v}]: "
              f"compute {rf['compute_s']:.3f}s  memory {rf['memory_s']:.3f}s  "
              f"coll {rf['collective_s']:.3f}s  -> {rf['dominant']} "
              f"(step {rf['step_time_s']:.3f}s, MFU {rf['mfu']:.1%}, "
              f"mem {mem:.1f} GiB)", flush=True)


if __name__ == "__main__":
    main()
