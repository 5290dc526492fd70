"""DeepSeek-V2-Lite through the simulator's normal path, on the CPU.

Latent attention (MLA), shared experts and a leading dense layer: the
program's GEMM lists against the plain layer equations of the benchmark's
``deepseek_v2`` adapter, the absorbed and explicit GEMM lists run as
numbers against a naive MLA forward, MACs against a count written out,
the three ``simulate_chip`` backends on a tiny stack, and the counters of
the benchmark cell's question against the host's counts.
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import check, run  # noqa: E402
from perfbench.arch import deepseek_v2  # noqa: E402
from repro.config import MLAConfig, ModelConfig, MoEConfig  # noqa: E402
from repro.core import probe  # noqa: E402
from repro.workload.compile import (CompileOptions,  # noqa: E402
                                    compile_workload)

CELL = "deepseek-v2-lite.mla-decode"
CONFIG = json.loads(
    (ROOT / "perfbench" / "configs" / "deepseek-v2-lite.json").read_text())


def _program_gemms(cfg, batch, seq, phase):
    model, opts = deepseek_v2.program(cfg)
    wl = compile_workload(model, batch=batch, seq=seq, phase=phase,
                          options=opts)
    return wl, [(s.M, s.K, s.N) for s in wl.specs]


# ------------------------------------------------------------ (a) GEMM lists

@pytest.mark.parametrize("scores", [True, False])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("batch", [1, 2, 3])
def test_program_gemms_equal_the_layer_equations(batch, phase, scores):
    cfg = {**CONFIG, "attention_scores": scores}
    seq = 64 if phase == "prefill" else 8192
    wl, got = _program_gemms(cfg, batch, seq, phase)
    want = deepseek_v2.layer_gemms(cfg, batch, seq, phase)
    assert got == [g[1:] for g in want]
    assert [s.name.split(".", 1)[1] for s in wl.specs] == [
        g[0] for g in want]


def test_blocks_by_layer_dense_lead_then_experts_with_shared():
    wl, _ = _program_gemms(CONFIG, 1, 8192, "decode")
    by_layer: dict[int, list] = {}
    for op in wl.ops:
        by_layer.setdefault(op.layer, []).append(op)
    assert sorted(by_layer) == [0, 1, 2, 3, 4]
    assert {op.block for op in by_layer[0]} == {"mla", "ffn"}
    for layer in range(1, 5):
        ops = by_layer[layer]
        assert {op.block for op in ops} == {"mla", "moe"}
        groups = [op.group for op in ops if op.block == "moe"]
        # six routed experts (one token, top-6) then the shared expert
        assert groups == [g for e in range(6) for g in [f"L{layer}.e{e}"] * 3
                          ] + [f"L{layer}.shared"] * 3
        shared = [op.spec for op in ops if op.group == f"L{layer}.shared"]
        assert [(s.M, s.K, s.N) for s in shared] == [
            (1, 2048, 2816), (1, 2048, 2816), (1, 2816, 2048)]


def test_an_unmodelled_query_path_is_refused_in_both():
    cfg = {**CONFIG, "q_lora_rank": 1536}
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        deepseek_v2.program(cfg)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        deepseek_v2.layer_gemms(cfg, 1, 128, "decode")


# ------------------------------------------------------------ (b) numerics

#: a tiny MLA: hidden d, h heads, latent r, rotary p, no-rope n, value v
TINY = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
        "qk_rope_head_dim": 8, "qk_nope_head_dim": 8, "v_head_dim": 8,
        "attention_scores": True}


def _weights(rng):
    d, h = TINY["hidden_size"], TINY["num_attention_heads"]
    r, p = TINY["kv_lora_rank"], TINY["qk_rope_head_dim"]
    n, v = TINY["qk_nope_head_dim"], TINY["v_head_dim"]
    return {"wq": rng.standard_normal((d, h * (n + p))) / np.sqrt(d),
            "wdkv": rng.standard_normal((d, r + p)) / np.sqrt(d),
            "wukv": rng.standard_normal((r, h * (n + v))) / np.sqrt(r),
            "wo": rng.standard_normal((h * v, d)) / np.sqrt(h * v)}


def _softmax(s):
    e = np.exp(s - s.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


def _heads(w):
    """Per-head views of the weights, as DeepSeek-V2 lays them out: each
    head's query is ``[nope | rope]``, each head's up-projection
    ``[k_nope | v]``."""
    h, r = TINY["num_attention_heads"], TINY["kv_lora_rank"]
    n, p, v = (TINY["qk_nope_head_dim"], TINY["qk_rope_head_dim"],
               TINY["v_head_dim"])
    wq = w["wq"].reshape(-1, h, n + p)
    wukv = w["wukv"].reshape(r, h, n + v)
    return (wq[..., :n], wq[..., n:], wukv[..., :n], wukv[..., n:],
            w["wo"].reshape(h, v, -1))


def _naive_decode(w, x, cache):
    """One new token per sequence attending to its latent cache plus its
    own new latent, with keys and values up-projected from the latent
    through W^UK and W^UV (the paper's eqs. 9-19, no absorption)."""
    r, n = TINY["kv_lora_rank"], TINY["qk_nope_head_dim"]
    p = TINY["qk_rope_head_dim"]
    wq_n, wq_r, wuk, wuv, wo = _heads(w)
    out = []
    for i in range(x.shape[0]):
        lat = np.vstack([cache[i], x[i] @ w["wdkv"]])   # [ctx, r + p]
        c, k_rope = lat[:, :r], lat[:, r:]
        k_nope = np.einsum("tr,rhn->htn", c, wuk)
        val = np.einsum("tr,rhv->htv", c, wuv)
        q_nope = np.einsum("d,dhn->hn", x[i], wq_n)
        q_rope = np.einsum("d,dhp->hp", x[i], wq_r)
        s = (np.einsum("hn,htn->ht", q_nope, k_nope)
             + q_rope @ k_rope.T) / np.sqrt(n + p)
        o = np.einsum("ht,htv->hv", _softmax(s), val)
        out.append(np.einsum("hv,hvd->d", o, wo))
    return np.array(out)


def _absorbed_weights(w):
    wq_n, wq_r, wuk, wuv, wo = _heads(w)
    h = TINY["num_attention_heads"]
    # W^UK folded into the query, W^UV into the output projection
    q_abs = np.concatenate([np.einsum("dhn,rhn->dhr", wq_n, wuk), wq_r],
                           axis=2).reshape(wq_n.shape[0], -1)
    o_abs = np.einsum("rhv,hvd->hrd", wuv, wo).reshape(h * wuv.shape[0], -1)
    return q_abs, o_abs


def _run_listed(gemms, operands):
    """Run ``gemms`` (the adapter's ``(name, M, K, N)`` list) in order:
    ``operands[name](state)`` gives the GEMM's ``A`` and ``B``, whose
    shapes must be the listed ones, and its product is appended to
    ``state[name]``."""
    state: dict[str, list] = {}
    for name, M, K, N in gemms:
        a, b = operands[name](state)
        assert a.shape == (M, K) and b.shape == (K, N), name
        state.setdefault(name, []).append(a @ b)
    return state


def test_absorbed_decode_gemms_match_a_naive_mla_forward():
    """The listed GEMMs of absorbed decode compute what the naive forward
    computes; the two differ only by reassociation in float64, so they
    agree to 1e-10 relative."""
    rng = np.random.default_rng(20240507)
    batch, ctx = 3, 12
    d, h = TINY["hidden_size"], TINY["num_attention_heads"]
    r, p = TINY["kv_lora_rank"], TINY["qk_rope_head_dim"]
    n = TINY["qk_nope_head_dim"]
    w = _weights(rng)
    x = rng.standard_normal((batch, d))
    cache = rng.standard_normal((batch, ctx - 1, r + p))
    q_abs, o_abs = _absorbed_weights(w)
    gemms = deepseek_v2.mla(TINY, batch, ctx, "decode")
    assert [g[0] for g in gemms] == (
        ["mla.q_absorbed", "mla.kv_a"]
        + ["mla.scores", "mla.context"] * batch + ["mla.o_absorbed"])

    def latent(st):
        # the cache of the sequence whose scores are next, with the new
        # token's latent row appended
        i = len(st.get("mla.scores", []))
        return np.vstack([cache[i], st["mla.kv_a"][0][i]])

    def scores(st):
        i = len(st.get("mla.scores", []))
        return st["mla.q_absorbed"][0][i].reshape(h, r + p), latent(st).T

    def context(st):
        i = len(st["mla.context"]) if "mla.context" in st else 0
        prob = _softmax(st["mla.scores"][i] / np.sqrt(n + p))
        lat = np.vstack([cache[i], st["mla.kv_a"][0][i]])
        return prob, lat[:, :r]

    def out(st):
        return np.stack([c.reshape(-1) for c in st["mla.context"]]), o_abs

    st = _run_listed(gemms, {
        "mla.q_absorbed": lambda st: (x, q_abs),
        "mla.kv_a": lambda st: (x, w["wdkv"]),
        "mla.scores": scores, "mla.context": context,
        "mla.o_absorbed": out})
    got, want = st["mla.o_absorbed"][0], _naive_decode(w, x, cache)
    assert np.linalg.norm(got - want) <= 1e-10 * np.linalg.norm(want)


def _naive_prefill(w, x, batch, seq):
    """Causal self-attention over each prompt, keys and values
    up-projected from the latent (the paper's explicit form)."""
    r, n = TINY["kv_lora_rank"], TINY["qk_nope_head_dim"]
    p = TINY["qk_rope_head_dim"]
    wq_n, wq_r, wuk, wuv, wo = _heads(w)
    mask = np.triu(np.full((seq, seq), -np.inf), 1)
    out = []
    for i in range(batch):
        xs = x[i * seq:(i + 1) * seq]
        lat = xs @ w["wdkv"]
        c, k_rope = lat[:, :r], lat[:, r:]
        k_nope = np.einsum("tr,rhn->htn", c, wuk)
        val = np.einsum("tr,rhv->htv", c, wuv)
        q_nope = np.einsum("sd,dhn->hsn", xs, wq_n)
        q_rope = np.einsum("sd,dhp->hsp", xs, wq_r)
        s = (np.einsum("hsn,htn->hst", q_nope, k_nope)
             + np.einsum("hsp,tp->hst", q_rope, k_rope)) / np.sqrt(n + p)
        o = np.einsum("hst,htv->shv", _softmax(s + mask), val)
        out.append(np.einsum("shv,hvd->sd", o, wo))
    return np.vstack(out)


def test_explicit_prefill_gemms_match_a_naive_mla_forward():
    """The listed GEMMs of the explicit prefill path, one scores and one
    context GEMM per (sequence, head), compute the naive forward; the
    two differ only by reassociation in float64."""
    rng = np.random.default_rng(20240508)
    batch, seq = 2, 12
    d, h = TINY["hidden_size"], TINY["num_attention_heads"]
    r, p = TINY["kv_lora_rank"], TINY["qk_rope_head_dim"]
    n, v = TINY["qk_nope_head_dim"], TINY["v_head_dim"]
    w = _weights(rng)
    x = rng.standard_normal((batch * seq, d))
    mask = np.triu(np.full((seq, seq), -np.inf), 1)
    gemms = deepseek_v2.mla(TINY, batch, seq, "prefill")

    def pair(st, key):
        k = len(st.get(key, []))
        return k // h, k % h, slice(k // h * seq, (k // h + 1) * seq)

    def scores(st):
        i, j, rows = pair(st, "mla.scores")
        q = st["mla.q"][0][rows].reshape(seq, h, n + p)[:, j]
        kv = st["mla.kv_b"][0][rows].reshape(seq, h, n + v)[:, j, :n]
        k_rope = st["mla.kv_a"][0][rows, r:]
        return q, np.hstack([kv, k_rope]).T

    def context(st):
        i, j, rows = pair(st, "mla.context")
        prob = _softmax(st["mla.scores"][i * h + j] / np.sqrt(n + p) + mask)
        return prob, st["mla.kv_b"][0][rows].reshape(seq, h, n + v)[:, j, n:]

    def out(st):
        o = np.stack(st["mla.context"]).reshape(batch, h, seq, v)
        return o.transpose(0, 2, 1, 3).reshape(batch * seq, h * v), w["wo"]

    st = _run_listed(gemms, {
        "mla.q": lambda st: (x, w["wq"]),
        "mla.kv_a": lambda st: (x, w["wdkv"]),
        "mla.kv_b": lambda st: (st["mla.kv_a"][0][:, :r], w["wukv"]),
        "mla.scores": scores, "mla.context": context, "mla.o": out})
    got, want = st["mla.o"][0], _naive_prefill(w, x, batch, seq)
    assert np.linalg.norm(got - want) <= 1e-10 * np.linalg.norm(want)


# ------------------------------------------------------------ (c) MACs

@pytest.mark.parametrize("batch", [1, 2])
def test_macs_of_a_decode_layer_at_published_widths(batch):
    wl, _ = _program_gemms(CONFIG, batch, 8192, "decode")
    mla = sum(op.spec.macs for op in wl.ops
              if op.layer == 1 and op.block == "mla")
    moe = sum(op.spec.macs for op in wl.ops
              if op.layer == 1 and op.block == "moe")
    dense = sum(op.spec.macs for op in wl.ops
                if op.layer == 0 and op.block == "ffn")
    # d 2048, 16 heads, latent 512 + rope 64, context 8192 per sequence
    assert mla == (batch * 2048 * 16 * 576        # absorbed query
                   + batch * 2048 * 576           # latent and rope key
                   + batch * 16 * 576 * 8192      # scores over the cache
                   + batch * 16 * 8192 * 512      # context in the latent
                   + batch * 16 * 512 * 2048)     # absorbed output
    assert mla == batch * 179_437_568
    # six routed experts a token, each one token here, and two shared
    # experts fused into one SwiGLU of 2816
    assert moe == 6 * batch * 3 * 2048 * 1408 + batch * 3 * 2048 * 2816
    assert dense == batch * 3 * 2048 * 10944


# ------------------------------------------------------------ (d) backends

def _tiny_stack() -> ModelConfig:
    return ModelConfig(
        name="tiny-mla", family="moe", n_layers=2, d_model=64, vocab=128,
        n_heads=4, n_kv_heads=4, d_ff=96,
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32, n_shared=1),
        mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8),
        first_dense=1)


@pytest.mark.parametrize("phase,batch,seq", [("decode", 2, 24),
                                             ("prefill", 1, 16)])
def test_tiny_mla_stack_has_identical_makespans_on_every_backend(
        phase, batch, seq):
    from repro.multicore.chip import ChipConfig, simulate_chip

    wl = compile_workload(_tiny_stack(), batch=batch, seq=seq, phase=phase,
                          options=CompileOptions(attention_scores=True))
    assert {op.block for op in wl.ops} == {"mla", "ffn", "moe"}
    assert any(op.group == "L1.shared" for op in wl.ops)
    reports = {be: simulate_chip(wl, ChipConfig(n_cores=2, backend=be,
                                                bw_bytes_per_cycle=128))
               for be in ("reference", "numpy", "jax")}
    ref = reports["reference"]
    for be, rep in reports.items():
        assert rep.cycles == pytest.approx(ref.cycles), be
        assert rep.per_core_cycles == pytest.approx(ref.per_core_cycles), be
        assert rep.macs == ref.macs == wl.macs


# ------------------------------------------------------------ (e) counters

def test_compile_counters_name_the_new_blocks():
    before = probe.COUNTS.copy()
    _program_gemms(CONFIG, 1, 8192, "decode")
    got = {k: probe.COUNTS[k] - before[k] for k in (
        "sim.gemms", "sim.mla_gemms", "sim.expert_gemms",
        "sim.dense_lead_gemms")}
    assert got == {"sim.gemms": 112, "sim.mla_gemms": 25,
                   "sim.expert_gemms": 84, "sim.dense_lead_gemms": 3}
    c = run.load_cell("qwen3-1.7b.sweep-prefill")
    model, opts = c["arch"].program(c["config"])
    before = probe.COUNTS.copy()
    compile_workload(model, batch=1, seq=128, phase="prefill", options=opts)
    assert {k: probe.COUNTS[k] - before[k] for k in (
        "sim.gemms", "sim.mla_gemms", "sim.expert_gemms",
        "sim.dense_lead_gemms")} == {
        "sim.gemms": 5, "sim.mla_gemms": 0, "sim.expert_gemms": 0,
        "sim.dense_lead_gemms": 0}


def test_the_cells_question_scans_as_counted_and_answers_correctly():
    """The harness's question, answered as the timed path answers it:
    133 chunk calls, 115 of them on a stream the question already
    scanned, and 0.487 of the scanned steps padding, as the host counts
    from the GEMM list at every context of the mix; every answer equal
    to the plain reference."""
    import repro.core as core

    from perfbench.questions import questions

    c = run.load_cell(CELL)
    model, opts = c["arch"].program(c["config"])
    seed = 2**31 + 1801
    q = next(questions(c["mix"], c["table"], seed))
    #: rasa_mm per design at each context of the mix (16x32x16 tiles)
    n_mm_at = {8128: 1_118_040, 8160: 1_118_380, 8192: 1_118_720}
    assert (q["batch"], q["phase"]) == (1, "decode")
    assert sorted(c["mix"]["seq"]) == sorted(n_mm_at)
    wl = compile_workload(model, batch=1, seq=q["seq"], phase="decode",
                          options=opts)
    fields = set(core.EngineConfig.__dataclass_fields__)
    cfgs = [core.EngineConfig(**{k: v for k, v in d.items() if k in fields})
            for d in q["designs"]]
    before = probe.COUNTS.copy()
    grid = core.sweep_workload(list(wl.specs), cfgs, backend="fast")
    got = {k: probe.COUNTS[k] - before[k] for k in probe.COUNTS}
    n_mm = [np.prod(s.tiles()) for s in wl.specs]
    assert sum(n_mm) == n_mm_at[q["seq"]]
    assert got["sim.chunk_calls"] == 133
    assert got["sim.repeat_chunk_calls"] == 115
    assert 1 - got["sim.useful_steps"] / got["sim.scan_steps"] == \
        pytest.approx(0.487, abs=5e-4)
    reader = run._metric_reader("repeat_call_share")
    assert reader({"counters": got}) == pytest.approx(115 / 133)
    rows = [{name: {"cycles": r.cycles, "n_mm": r.n_mm, "n_tl": r.n_tl,
                    "n_ts": r.n_ts, "wl_skips": r.wl_skips,
                    "utilization": r.utilization}
             for name, r in row.items()} for row in grid]
    answered = [{**q, "results": rows,
                 "gemms": [(s.name, s.M, s.K, s.N) for s in wl.specs]}]
    assert check.passed(check.compare(answered, c, seed))


def test_equal_shapes_share_a_shape_key_whatever_their_names():
    from repro.core.tiling import ALG1_POLICY, GemmSpec
    from repro.core.trace import gemm_trace, shape_key

    gate = gemm_trace(GemmSpec("moe.gate", 1, 2048, 1408), ALG1_POLICY)
    up = gemm_trace(GemmSpec("moe.up", 1, 2048, 1408), ALG1_POLICY)
    down = gemm_trace(GemmSpec("moe.down", 1, 1408, 2048), ALG1_POLICY)
    assert gate is not up and shape_key(gate) == shape_key(up)
    assert shape_key(down) != shape_key(gate)
    # a trace made otherwise is keyed by itself alone
    padded = gate.padded(len(gate) + 1)
    assert shape_key(padded) is padded


def test_repeat_call_share_reads_nothing_from_a_program_without_it():
    reader = run._metric_reader("repeat_call_share")
    assert reader({"counters": {"sim.chunk_calls": 48}}) is None
    assert reader({"counters": None}) is None
    assert reader({"counters": {"sim.chunk_calls": 4,
                                "sim.repeat_chunk_calls": 1}}) == 0.25
