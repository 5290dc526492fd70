"""Blockwise (flash) causal attention as a Pallas TPU kernel.

Used for the long-prefill shapes (prefill_32k): attention logits are never
materialized; running max / sum-of-exp / weighted accumulator live in VMEM
scratch across the kv-block loop.  The kv loop is the innermost grid axis,
so k/v block copies are prefetched by the Pallas pipeline during compute --
the same WLS-style overlap the RASA schedule uses for weights.

Layout: q [BH, Sq, D], k/v [BH, Skv, D] (batch*heads flattened; GQA handled
by the ops.py wrapper).  fp32 softmax state, output cast to q.dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, bq: int, bkv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bkv, d]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bkv]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            cols = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                               # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [bq, bkv]
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked kv blocks (upper triangle)
        pl.when(ki * bkv <= qi * bq + bq - 1)(body)
    else:
        body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D], k/v: [BH, Skv, D] -> [BH, Sq, D]."""
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, "ops.py pads to block multiples"
    if scale is None:
        scale = d ** -0.5

    grid = (bh, sq // bq, skv // bkv)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bkv=bkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
