"""``deepseek_v2``: multi-head latent attention (MLA) in every layer, a
dense SwiGLU FFN in the first ``first_k_dense_replace`` layers, then
routed SwiGLU experts beside ``n_shared_experts`` shared ones.

The layer equations are DeepSeek-V2's (arXiv:2405.04434, §2.1), with
``h`` heads, latent width ``r`` (``kv_lora_rank``), rotary width ``p``,
no-rope width ``n`` and value width ``v`` per head:

* prefill, the explicit path: the query ``x W^Q`` (``h * (n + p)``), the
  compressed latent and shared rotary key ``x W^DKV`` (``r + p``), the
  per-head keys and values ``c W^UKV`` (``h * (n + v)``), and the output
  ``W^O`` (``h * v``); with ``attention_scores`` a ``Q_h K_h^T`` and a
  ``P_h V_h`` GEMM for each head of each sequence, since each head has
  keys of its own;
* decode, the absorbed path: ``W^UK`` folded into the query projection
  (``h * (r + p)`` outputs) and ``W^UV`` into the output projection
  (``h * r`` inputs); with ``attention_scores`` one scores GEMM
  ``(h, r + p, ctx)`` and one context GEMM ``(h, ctx, r)`` per sequence,
  since every head of a sequence reads that sequence's latent cache.

The query's low-rank path (``q_lora_rank``) and MoE layers at a stride
(``moe_layer_freq`` above 1) are not modelled, and a configuration that
sets either is refused. Routing is balanced, as in
``_gqa.experts``. ``layer_gemms`` imports nothing of ``repro``.
"""

from __future__ import annotations

import math

from perfbench.arch._gqa import ffn, head, tokens

Gemm = tuple[str, int, int, int]


def _refuse_unmodelled(cfg: dict) -> None:
    if cfg["q_lora_rank"] is not None:
        raise NotImplementedError("q_lora_rank: the low-rank query path "
                                  "is not modelled")
    if cfg["moe_layer_freq"] != 1:
        raise NotImplementedError("moe_layer_freq: experts are modelled in "
                                  "every layer after the dense ones")


def mla(cfg: dict, batch: int, seq: int, phase: str) -> list[Gemm]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, p = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    n, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    m = tokens(batch, seq, phase)
    scores = cfg["attention_scores"]
    if phase == "prefill":
        out = [("mla.q", m, d, h * (n + p)), ("mla.kv_a", m, d, r + p),
               ("mla.kv_b", m, r, h * (n + v))]
        if scores:
            out += [("mla.scores", seq, n + p, seq),
                    ("mla.context", seq, seq, v)] * (batch * h)
        return out + [("mla.o", m, h * v, d)]
    out = [("mla.q_absorbed", m, d, h * (r + p)), ("mla.kv_a", m, d, r + p)]
    if scores:
        out += [("mla.scores", h, r + p, seq),
                ("mla.context", h, seq, r)] * batch
    return out + [("mla.o_absorbed", m, h * r, d)]


def experts(cfg: dict, m: int) -> list[Gemm]:
    """Routed experts under balanced routing, then the shared experts as
    one SwiGLU of ``n_shared_experts`` times the expert width."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed = m * cfg["num_experts_per_tok"]
    held = min(cfg["n_routed_experts"], routed)
    m_e = math.ceil(routed / held)
    shared = cfg["n_shared_experts"] * ff
    return ([("moe.gate", m_e, d, ff), ("moe.up", m_e, d, ff),
             ("moe.down", m_e, ff, d)] * held
            + [("moe.shared.gate", m, d, shared),
               ("moe.shared.up", m, d, shared),
               ("moe.shared.down", m, shared, d)])


def layer_gemms(cfg: dict, batch: int, seq: int, phase: str) -> list[Gemm]:
    """``(name, M, K, N)`` of every GEMM, layer by layer."""
    _refuse_unmodelled(cfg)
    m = tokens(batch, seq, phase)
    out: list[Gemm] = []
    for layer in range(cfg["num_hidden_layers"]):
        out += mla(cfg, batch, seq, phase) + (
            experts(cfg, m) if layer >= cfg["first_k_dense_replace"]
            else ffn(cfg, m))
    return out + head(cfg, m)


def program(cfg: dict):
    """The program's ``(ModelConfig, CompileOptions)`` for ``cfg``."""
    from repro.config import MLAConfig, ModelConfig, MoEConfig
    from repro.workload.compile import CompileOptions

    _refuse_unmodelled(cfg)
    model = ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], vocab=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        act={"silu": "swiglu"}[cfg["hidden_act"]],
        moe=MoEConfig(n_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        first_dense=cfg["first_k_dense_replace"])
    return model, CompileOptions(include_head=cfg["lm_head"],
                                 attention_scores=cfg["attention_scores"])
