"""Public jit'd wrappers around the Pallas kernels.

Handles: interpret mode on CPU, padding to block multiples, GQA head
expansion, and batched (3D+) matmul via vmap-free reshapes.  Models call
these through ``repro.models.common.matmul`` so the engine is selectable per
config (``xla`` | ``pallas_rasa``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .rasa_gemm import GemmBlocks, default_blocks, rasa_gemm


def _interpret_default() -> bool:
    """Interpret mode on the CPU (tests), compiled Mosaic on the TPU.

    Any other backend is an error rather than a silent interpreter run, so a
    device that is not a TPU is never mistaken for one.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels run on 'tpu' (or interpreted on "
                       f"'cpu'), not on {backend!r}")


def _pad_to(x: jax.Array, mult: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=("schedule", "blocks", "interpret",
                                             "out_dtype"))
def rasa_matmul(a: jax.Array, b: jax.Array, c: jax.Array | None = None,
                *, schedule: str = "wls", blocks: GemmBlocks | None = None,
                out_dtype: jnp.dtype = jnp.float32,
                interpret: bool | None = None) -> jax.Array:
    """C (+)= A @ B via the RASA-scheduled Pallas kernel, any 2D shapes.

    Pads to block multiples (zero padding is exact for matmul) and strips.
    ``interpret=None`` interprets on the CPU and compiles on the TPU.
    """
    if interpret is None:
        interpret = _interpret_default()
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    blocks = blocks or default_blocks(m, k, n)
    ap = _pad_to(a, (blocks.bm, blocks.bk))
    bp = _pad_to(b, (blocks.bk, blocks.bn))
    cp = None if c is None else _pad_to(c.astype(out_dtype),
                                        (blocks.bm, blocks.bn))
    out = rasa_gemm(ap, bp, cp, schedule=schedule, blocks=blocks,
                    out_dtype=out_dtype, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_kv", "interpret"))
def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array,
              *, causal: bool = True, scale: float | None = None,
              block_q: int = 512, block_kv: int = 512,
              interpret: bool | None = None) -> jax.Array:
    """GQA flash attention: q [B,Hq,S,D], k/v [B,Hkv,S,D] -> [B,Hq,S,D].

    kv heads are broadcast to query groups; sequence dims padded to block
    multiples (padded kv positions masked out by causality for the padded
    query rows; padded q rows are stripped).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    # zero-padded kv positions are only sound when masked by causality
    assert causal or (sq % min(block_q, sq) == 0
                      and k.shape[2] % min(block_kv, k.shape[2]) == 0)
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)

    bq = min(block_q, max(128, 1 << (sq - 1).bit_length()))
    bkv = min(block_kv, max(128, 1 << (k.shape[2] - 1).bit_length()))
    qp = _pad_to(q, (1, 1, bq, 1))
    kp = _pad_to(k, (1, 1, bkv, 1))
    vp = _pad_to(v, (1, 1, bkv, 1))
    sqp, skvp = qp.shape[2], kp.shape[2]

    out = flash_attention(
        qp.reshape(b * hq, sqp, d),
        kp.reshape(b * hq, skvp, d),
        vp.reshape(b * hq, skvp, d),
        causal=causal, scale=scale, block_q=bq, block_kv=bkv,
        interpret=interpret)
    return out.reshape(b, hq, sqp, d)[:, :, :sq, :]
