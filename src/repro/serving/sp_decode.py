"""Sequence-parallel flash decode (shard_map) for 500k-context serving.

The KV cache is sharded along the *sequence* axis over "data"; each shard
computes local attention with a local logsumexp, and the shards are
combined with the numerically-exact flash-decoding reduction:

    out = sum_i exp(lse_i - lse) out_i,   lse = logsumexp_i(lse_i)

One psum of [B, H, D+2] per layer instead of gathering a 500k-long score
row (or worse, the cache) -- this is the collective-term optimization
recorded in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _local_decode(q, k, v, start, lengths, scale):
    """q: [B,H,hd]; k/v: [B,H,Sl,hd] (local shard); start: scalar global
    offset of this shard; lengths: [B] valid global lengths.
    Returns (out [B,H,hd], lse [B,H])."""
    s_local = k.shape[2]
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    pos = start + jnp.arange(s_local)[None, None, :]
    mask = pos < lengths[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)                          # [B,H]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p, v.astype(jnp.float32))
    # locally-normalized output + logsumexp (guard fully-masked shards)
    out = out / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    return out, lse


def sp_flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    lengths: jax.Array, mesh: Mesh, *,
                    seq_axis: str = "data",
                    scale: float | None = None) -> jax.Array:
    """Decode attention over a sequence-sharded KV cache.

    q: [B, H, hd] (replicated over seq shards); caches [B, H, S, hd] sharded
    on S over `seq_axis`; lengths [B].  GQA expansion happens before the
    call.  Returns [B, H, hd].
    """
    b, h, hd = q.shape
    s = k_cache.shape[2]
    if scale is None:
        scale = hd ** -0.5
    n_shards = mesh.shape[seq_axis]
    s_local = s // n_shards

    def shard_fn(q_l, k_l, v_l, len_l):
        idx = jax.lax.axis_index(seq_axis)
        start = idx * s_local
        out, lse = _local_decode(q_l, k_l, v_l, start, len_l, scale)
        # flash-decoding combine across shards
        g_max = jax.lax.pmax(lse, seq_axis)
        g_max = jnp.where(jnp.isfinite(g_max), g_max, 0.0)
        w = jnp.exp(jnp.where(jnp.isfinite(lse), lse - g_max, -jnp.inf))
        num = jax.lax.psum(out * w[..., None], seq_axis)
        den = jax.lax.psum(w, seq_axis)
        return (num / jnp.maximum(den[..., None], 1e-30)).astype(q.dtype)

    # head sharding over model when divisible; sequence over `seq_axis`
    hm = "model" if ("model" in mesh.axis_names
                     and h % mesh.shape["model"] == 0) else None
    spec_q = P(None, hm, None)
    spec_kv = P(None, hm, seq_axis, None)
    fn = jax.shard_map(shard_fn, mesh=mesh,
                   in_specs=(spec_q, spec_kv, spec_kv, P()),
                   out_specs=spec_q,
                   check_vma=False)
    return fn(q, k_cache, v_cache, lengths)
