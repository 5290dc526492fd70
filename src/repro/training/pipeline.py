"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The layer stack is split into ``n_stages`` contiguous stages; stage s lives
on the mesh slice ``axis == s`` (stage-dim-sharded stacked params).
Microbatches stream through: at tick t, stage s computes microbatch
t - s (bubble at the ends -- the classic GPipe schedule), then activations
collective-permute to the next stage.

This composes with the other axes: on the (2,16,16) production mesh,
``axis="pod"`` gives 2 pipeline stages, each sharded FSDP x TP over
(data, model) within its pod -- inter-pod traffic becomes the activation
ppermute instead of FSDP all-gathers, which is the right trade when
inter-pod links are the slow tier (DCN).  See EXPERIMENTS.md §Perf.

API:
    y = pipeline_apply(stage_params, x, stage_fn, mesh,
                       axis="pod", n_microbatches=m)
where stage_params leaves are [n_stages, ...] and
``stage_fn(params_slice, x_mb) -> y_mb``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_params: Any, x: jax.Array,
                   stage_fn: Callable[[Any, jax.Array], jax.Array],
                   mesh: Mesh, *, axis: str = "pod",
                   n_microbatches: int | None = None) -> jax.Array:
    """Run x [B, ...] through the staged computation; returns y [B, ...].

    Stage params: pytree with leading [n_stages] dim (sharded over `axis`).
    The batch is split into n_microbatches (default = n_stages) along dim 0.
    """
    n_stages = mesh.shape[axis]
    m = n_microbatches or n_stages
    b = x.shape[0]
    assert b % m == 0, (b, m)
    mb = b // m

    x_mb = x.reshape(m, mb, *x.shape[1:])

    def staged(params_local, x_local):
        # params_local: this stage's slice (leading dim 1); x_local: the
        # full microbatch stream (replicated over `axis`)
        params_s = jax.tree.map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = m + n_stages - 1
        size = jax.lax.psum(1, axis)  # == n_stages

        def tick(carry, t):
            buf = carry                     # [mb, ...] current activation
            # stage 0 injects microbatch t from the input stream
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = x_local[mb_idx]
            cur = jnp.where(stage_id == 0, inject, buf)
            out = stage_fn(params_s, cur)
            # pass to the next stage (ring; last stage's output wraps to 0
            # where it is ignored/collected)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(out, axis, perm)
            # the LAST stage's outputs are the pipeline outputs, valid for
            # ticks in [n_stages-1, n_ticks); collect them on every device
            # (cheap: one microbatch per tick)
            done = out  # stage-local; only last stage's is meaningful
            return nxt, done

        _, outs = jax.lax.scan(tick, jnp.zeros_like(x_local[0]),
                               jnp.arange(n_ticks))
        # outs: [n_ticks, mb, ...] per stage; select the last stage's ticks
        # [s-1 .. s-1+m) -- psum the masked stream so every stage returns
        # the same assembled output
        is_last = stage_id == (size - 1)
        valid = outs[n_stages - 1:n_stages - 1 + m]
        contrib = jnp.where(is_last, valid, jnp.zeros_like(valid))
        y = jax.lax.psum(contrib, axis)
        return y

    y_mb = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_mb)
    return y_mb.reshape(b, *y_mb.shape[2:])


def split_stages(params: Any, n_stages: int) -> Any:
    """Reshape stacked per-layer params [L, ...] -> [n_stages, L/n_stages, ...]."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(f, params)
