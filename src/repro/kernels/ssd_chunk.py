"""Fused Mamba2 SSD chunk kernel (Pallas TPU).

This is the kernel the zamba2 hillclimb identified as the memory-term fix
(EXPERIMENTS.md §Perf B1.3): the XLA lowering of the chunked SSD spends
its HBM traffic on elementwise passes over [B,S,H,*] intermediates; this
kernel keeps one chunk's working set (scores [q,q] ~256 KiB + x/B/C/state
blocks ~1.3 MiB) in VMEM and streams only the operands.

Grid: (BH, nc) with the chunk axis sequential ("arbitrary") -- the running
inter-chunk state lives in a VMEM scratch accumulator across chunk steps,
exactly like the rasa_gemm "wls" schedule keeps its fp32 accumulator
(shadow-buffer analogy: the state is the stationary operand carried across
grid steps).

Layout (heads flattened into the grid):
  x:  [BH, S, P]    dt: [BH, S]    B/C: [BH, S, N]    A: scalar per (b,h)
  (A sits whole in SMEM; dt enters the kernel as [BH, 1, S] rows.)
Returns y [BH, S, P] and the final state [BH, P, N].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, fin_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    # Per-step vectors are kept 2-D (a [1, q] row or a [q, 1] column): the
    # TPU tiles the last two dims, so 1-D blocks and a cumsum do not lower.
    a = a_ref[pl.program_id(0)]                    # scalar decay rate (<0)
    x = x_ref[0].astype(jnp.float32)               # [q, P]
    dt = dt_ref[0].astype(jnp.float32)             # [1, q]
    b = b_ref[0].astype(jnp.float32)               # [q, N]
    c = c_ref[0].astype(jnp.float32)               # [q, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA = dt * a                                    # [1, q] (negative)
    dA_col = jnp.sum(jnp.where(ii == jj, dA, 0.0), axis=1, keepdims=True)
    seg = jnp.sum(jnp.where(ii <= jj, dA_col, 0.0), axis=0,
                  keepdims=True)                   # [1, q] inclusive cumsum
    seg_col = jnp.sum(jnp.where(ii == jj, seg, 0.0), axis=1,
                      keepdims=True)               # [q, 1], the same values
    seg_last = jnp.sum(dA, axis=1, keepdims=True)  # [1, 1]

    # intra-chunk: scores[i,j] = c_i.b_j * exp(seg_i - seg_j) * dt_j, i >= j
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)   # [q, q]
    diff = jnp.where(ii >= jj, seg_col - seg, -1e30)
    w = cb * jnp.exp(diff) * dt                    # [q, q]
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)      # [q, P]

    # inter-chunk: y_i += (c_i . state_prev) * exp(seg_i)
    prev = state_ref[...]                          # [N, P]
    y = y + jnp.dot(c, prev,
                    preferred_element_type=jnp.float32) * jnp.exp(seg_col)

    # state update: state = exp(seg_last)*prev + sum_j b_j (dt_j x_j)^T decay_j
    wj = jnp.exp(seg_last - seg) * dt              # [1, q]
    st_c = jnp.dot(b.T * wj, x, preferred_element_type=jnp.float32)  # [N, P]
    state_ref[...] = prev * jnp.exp(seg_last) + st_c

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _fin():
        fin_ref[0] = state_ref[...].astype(fin_ref.dtype)


def ssd_chunk_fused(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, *, chunk: int = 256,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: [BH, S, P]; dt: [BH, S]; a: [BH]; b/c: [BH, S, N].

    Returns (y [BH, S, P], final_state [BH, N, P]).
    """
    bh, s, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, fin = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # a, whole
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),   # x
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),   # b
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),   # c
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),   # y
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),   # final state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, dt.reshape(bh, 1, s), b, c)
    return y, fin


def hbm_bytes_fused(bh: int, s: int, p: int, n: int,
                    in_bytes: int = 2) -> int:
    """Cost model: streamed operands only (x, dt, b, c in; y out; state
    negligible) -- the §Perf B1.3 napkin."""
    return bh * s * (2 * p + 2 * n + 1) * in_bytes + bh * n * p * 4
