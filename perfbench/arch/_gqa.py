"""The GQA + SwiGLU block, shared by ``qwen3`` and ``granitemoe``.

Every layer is the same: fused ``qkv`` and ``wo`` attention projections,
then a SwiGLU FFN (gate, up, down) or, where the configuration has
``num_local_experts``, routed SwiGLU experts under balanced routing.

``layer_gemms`` is the plain reference of these layer equations: it
imports nothing of ``repro``. ``program`` builds the program's own
``ModelConfig`` and ``CompileOptions`` for the same configuration.
The block functions (``attention``, ``ffn``, ``experts``, ``head``) take
the configuration and the token count ``m`` of one projection, so an
adapter whose layers differ can compose a stack from them.
"""

from __future__ import annotations

import math

Gemm = tuple[str, int, int, int]


def tokens(batch: int, seq: int, phase: str) -> int:
    """Rows of a projection GEMM: every prompt token in prefill, one new
    token per sequence in decode."""
    return batch * seq if phase == "prefill" else batch


def attention(cfg: dict, m: int) -> list[Gemm]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return [("attn.qkv", m, d, (h + 2 * kv) * hd),
            ("attn.wo", m, h * hd, d)]


def ffn(cfg: dict, m: int) -> list[Gemm]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return [("ffn.gate", m, d, ff), ("ffn.up", m, d, ff),
            ("ffn.down", m, ff, d)]


def experts(cfg: dict, m: int) -> list[Gemm]:
    """Balanced routing: the ``m * top_k`` routed tokens spread evenly over
    as many experts as there are tokens, at most all of them."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    routed = m * cfg["num_experts_per_tok"]
    held = min(cfg["num_local_experts"], routed)
    m_e = math.ceil(routed / held)
    return [("moe.gate", m_e, d, ff), ("moe.up", m_e, d, ff),
            ("moe.down", m_e, ff, d)] * held


def head(cfg: dict, m: int) -> list[Gemm]:
    return [("head", m, cfg["hidden_size"], cfg["vocab_size"])] \
        if cfg["lm_head"] else []


def layer_gemms(cfg: dict, batch: int, seq: int, phase: str) -> list[Gemm]:
    """``(name, M, K, N)`` of every projection GEMM, layer by layer."""
    if cfg["attention_scores"]:
        raise NotImplementedError("attention-score GEMMs have no reference")
    m = tokens(batch, seq, phase)
    mix = experts if cfg.get("num_local_experts", 0) else ffn
    layer = attention(cfg, m) + mix(cfg, m)
    return layer * cfg["num_hidden_layers"] + head(cfg, m)


def program(cfg: dict):
    """The program's ``(ModelConfig, CompileOptions)`` for ``cfg``."""
    from repro.config import ModelConfig, MoEConfig
    from repro.workload.compile import CompileOptions

    n_experts = cfg.get("num_local_experts", 0)
    moe = (MoEConfig(n_experts=n_experts, top_k=cfg["num_experts_per_tok"],
                     d_ff_expert=cfg["intermediate_size"])
           if n_experts else None)
    model = ModelConfig(
        name=cfg["name"], family="moe" if n_experts else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=0 if n_experts else cfg["intermediate_size"],
        head_dim=cfg["head_dim"],
        act={"silu": "swiglu"}[cfg["hidden_act"]], moe=moe)
    return model, CompileOptions(include_head=cfg["lm_head"],
                                 attention_scores=cfg["attention_scores"])
