"""The architecture adapters (``perfbench/arch/``), on the CPU.

They pin the GEMM lists of the three cells as the harness built them
before the adapters, run a configuration of a new ``model_type`` whose
layers differ through ``run_cell`` with files added and none edited,
see a ``model_type`` with no adapter refused before any question, and
see that the reference side of every adapter imports nothing of
``repro``.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import run  # noqa: E402
from perfbench.questions import questions  # noqa: E402

#: sha256 of the first 6 window questions of a cell and seed: each
#: question's batch, seq, phase and design names, the reference's
#: ``(name, M, K, N)`` list and the program's ``(M, K, N)`` list, as the
#: harness built them with ``reference.layer_gemms`` and
#: ``run.model_config`` before the adapters
PINNED = {
    ("qwen3-1.7b.sweep-prefill", 2147483901):
        "33d67cb39b23ac6036e406f02f870ad90050814a81b46e3b1217086647da4988",
    ("qwen3-1.7b.sweep-prefill", 3000000019):
        "6168ee49156206e1ffebe98bc31c1afa8956e22df9bee2e2c85047f4ee0710b5",
    ("granite-moe-3b-a800m.sweep-decode", 2147483901):
        "8eebc7adfe3d65014971232b9ae22ffeb86433807457e6942eee2cd4fbe81207",
    ("granite-moe-3b-a800m.sweep-decode", 3000000019):
        "29ac87cd109b3a01d2c14d50c279dfdcf2cc8fc1af322a115864bf6c31da6388",
    ("qwen3-1.7b.search-decode", 2147483901):
        "328aa7930caa5fb768a18a47a1d1d75651bfd6f1d92ed0d45d2ba409ebfe0b0d",
    ("qwen3-1.7b.search-decode", 3000000019):
        "77ff82ffb225e7da96f06ff97a9e1b54e64981ada05bcbc4fd961dd65f274fa2",
}


@pytest.mark.parametrize("cell,seed", sorted(PINNED))
def test_cells_ask_and_check_what_they_did_before_the_adapters(cell, seed):
    from repro.workload.compile import compile_workload

    c = run.load_cell(cell)
    model, opts = c["arch"].program(c["config"])
    qs = questions(c["mix"], c["table"], seed)
    rows = []
    for _ in range(6):
        q = next(qs)
        ref = c["arch"].layer_gemms(c["config"], q["batch"], q["seq"],
                                    q["phase"])
        wl = compile_workload(model, batch=q["batch"], seq=q["seq"],
                              phase=q["phase"], options=opts)
        rows.append([q["batch"], q["seq"], q["phase"],
                     [d["name"] for d in q["designs"]],
                     [list(g) for g in ref],
                     [[s.M, s.K, s.N] for s in wl.specs]])
    got = hashlib.sha256(json.dumps(rows).encode()).hexdigest()
    assert got == PINNED[(cell, seed)]


# ------------------------------------------------------------ a new model_type

#: a toy hybrid through the program's ``ModelConfig.ssm`` / ``hybrid``
#: path: every layer is a Mamba-2 block, and layers at multiples of
#: ``attn_layer_period`` add the GQA attention and a dense SwiGLU FFN
TOY_ADAPTER = '''
"""``toyhybrid``: Mamba-2 in every layer; attention and a dense SwiGLU
FFN in layers at multiples of ``attn_layer_period``."""

import math

from perfbench.arch import _gqa


def mamba2(cfg, batch, seq, phase):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    p, n = cfg["mamba_d_head"], cfg["mamba_d_state"]
    h = di // p
    m = _gqa.tokens(batch, seq, phase)
    out = [("ssm.in_proj", m, d, 2 * di + 2 * cfg["mamba_n_groups"] * n + h)]
    if phase == "prefill":
        q = min(cfg["mamba_chunk_size"], seq)
        nc = math.ceil(seq / q)
        rows = batch * h * nc * q
        out += [("ssm.cb", rows, n, q), ("ssm.intra", rows, q, p),
                ("ssm.inter", rows, n, p),
                ("ssm.state", batch * h * nc * n, q, p)]
    else:
        out += [("ssm.out", batch * h, n, p),
                ("ssm.state", batch * h * n, 1, p)]
    return out + [("ssm.out_proj", m, di, d)]


def layer_gemms(cfg, batch, seq, phase):
    m = _gqa.tokens(batch, seq, phase)
    out = []
    for layer in range(cfg["num_hidden_layers"]):
        out += mamba2(cfg, batch, seq, phase)
        if layer % cfg["attn_layer_period"] == 0:
            out += _gqa.attention(cfg, m) + _gqa.ffn(cfg, m)
    return out


def program(cfg):
    from repro.config import HybridConfig, ModelConfig, SSMConfig
    from repro.workload.compile import CompileOptions

    ssm = SSMConfig(d_state=cfg["mamba_d_state"], expand=cfg["mamba_expand"],
                    head_dim=cfg["mamba_d_head"],
                    n_groups=cfg["mamba_n_groups"],
                    chunk=cfg["mamba_chunk_size"])
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], head_dim=cfg["head_dim"],
        act="swiglu", ssm=ssm,
        hybrid=HybridConfig(attn_every=cfg["attn_layer_period"])), \\
        CompileOptions()
'''

TOY_CONFIG = {
    "name": "toy-hybrid", "model_type": "toyhybrid", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "vocab_size": 512, "num_hidden_layers": 2,
    "attn_layer_period": 2, "mamba_expand": 2, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_chunk_size": 64}


def _root_with(tmp_path: pathlib.Path, cfg: dict, traffic: str,
               adapter: str | None = None) -> str:
    """A copy of the benchmark's files under ``tmp_path`` with one more
    configuration and cell, and the adapter if given; the cell's name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"{cfg['name']}.{traffic}"
    bench["configs"].append({
        "name": cfg["name"], "source": "https://example.org/toy",
        "file": f"perfbench/configs/{cfg['name']}.json", "reduced": [],
        "why": "layers of two kinds"})
    bench["workloads"].append({"name": name, "config": cfg["name"],
                               "traffic": traffic, "chips": 1,
                               "why": "a new model_type added as files"})
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "perfbench" / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    if adapter is not None:
        (tmp_path / "perfbench" / "arch"
         / f"{cfg['model_type']}.py").write_text(adapter)
    return name


@pytest.mark.parametrize("traffic", ["sweep-decode", "sweep-prefill"])
def test_a_new_model_type_with_two_kinds_of_layer_runs_from_files(
        tmp_path, traffic):
    import jax

    name = _root_with(tmp_path, TOY_CONFIG, traffic, TOY_ADAPTER)
    c = run.load_cell(name, tmp_path)
    kinds = {g[0].split(".")[0] for g in c["arch"].layer_gemms(
        TOY_CONFIG, 1, 128, c["mix"]["phase"])}
    assert kinds == {"ssm", "attn", "ffn"}
    out = run.run_cell(c, 2**31 + 21, 0.0, False, jax.devices()[:1])
    assert out["correct"] is True and out["attempted"] >= 1
    assert out["checks"]["shape_mismatch"]["value"] == 0


def test_a_model_type_without_an_adapter_is_refused_before_set_up(
        tmp_path, monkeypatch, capsys):
    cfg = {**TOY_CONFIG, "name": "no-adapter", "model_type": "noadapter"}
    name = _root_with(tmp_path, cfg, "sweep-decode")
    looked_for = tmp_path / "perfbench" / "arch" / "noadapter.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(looked_for))):
        run.load_cell(name, tmp_path)

    def boom(*a, **k):
        raise AssertionError("set-up ran for a refused configuration")

    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "run_cell", boom)
    rc = run.main(["--workload", name, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "refused" in out.err and str(looked_for) in out.err


def test_adapter_references_import_nothing_of_the_program():
    code = textwrap.dedent(f"""
        import importlib.util, json, pathlib, sys
        root = pathlib.Path({str(ROOT)!r})
        sys.path.insert(0, str(root))
        def load(path):
            spec = importlib.util.spec_from_file_location(
                "arch_" + path.stem, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
        for path in sorted((root / "perfbench" / "arch").glob("*.py")):
            load(path)
        calls = 0
        for f in sorted((root / "perfbench" / "configs").glob("*.json")):
            cfg = json.loads(f.read_text())
            arch = load(root / "perfbench" / "arch"
                        / (cfg["model_type"] + ".py"))
            for phase in ("prefill", "decode"):
                calls += len(arch.layer_gemms(cfg, 2, 64, phase)) > 0
        print(calls, sorted(m for m in sys.modules
                            if m == "repro" or m.startswith("repro.")))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120)
    calls, loaded = res.stdout.strip().split(" ", 1)
    assert int(calls) >= 4 and loaded == "[]"
