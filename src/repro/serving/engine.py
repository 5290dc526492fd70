"""Batched serving: jitted prefill + decode steps with cache shardings,
and a small session wrapper that serves batched requests (examples/serve_lm
drives it; tests check greedy decoding end-to-end).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.sharding import (MeshContext, activation_spec,
                                    kv_cache_spec, param_specs)
from ..models import ModelApi
from ..models.layers import KVCache
from ..models.ssm import SSMState


def decode_state_shardings(api: ModelApi, state: Any, ctx: MeshContext):
    """Shardings for a decode state pytree: KV caches via kv_cache_spec
    (with the leading stacking dim), SSM states batch-over-dp (or
    replicated when batch doesn't divide, e.g. long_500k batch=1),
    scalars replicated."""
    m = api.model
    mesh = ctx.mesh
    sp = ctx.parallel.sequence_parallel_decode
    dp = ctx.dp_axes
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def batch_axes(b):
        return dp if b % dp_size == 0 else None

    def spec_for(leaf):
        shape = leaf.shape
        if (leaf.ndim == 5 and m.n_kv_heads
                and shape[2] == m.n_kv_heads
                and shape[4] == m.resolved_head_dim):
            # stacked KV cache [L(or apps), B, Hkv, S, hd]
            base = kv_cache_spec(m.n_kv_heads, m.resolved_head_dim, ctx,
                                 sequence_parallel=sp)
            base = list(base) + [None] * (4 - len(base))
            if base[0] is not None and shape[1] % dp_size != 0:
                base[0] = None      # batch too small to shard
            return P(None, *base)
        if m.ssm is not None and leaf.ndim in (4, 5) and shape[0] == m.n_layers:
            # ssm state [L, B, H, P, N] / conv window [L, B, k-1, C]
            return P(None, batch_axes(shape[1]), *(None,) * (leaf.ndim - 2))
        return P()

    specs = jax.tree.map(spec_for, state)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def jit_prefill(api: ModelApi, ctx: MeshContext, state_template: Any):
    params_sh = _params_shardings(api, ctx)
    st_sh = decode_state_shardings(api, state_template, ctx)
    tok_sh = NamedSharding(ctx.mesh, activation_spec("tokens", ctx))
    return jax.jit(api.prefill,
                   in_shardings=(params_sh, tok_sh, st_sh),
                   out_shardings=(None, st_sh),
                   donate_argnums=(2,))


def jit_decode_step(api: ModelApi, ctx: MeshContext, state_template: Any):
    params_sh = _params_shardings(api, ctx)
    st_sh = decode_state_shardings(api, state_template, ctx)
    dp = ctx.dp_axes
    tok_sh = NamedSharding(
        ctx.mesh, P(dp) if api.model.family != "audio" else P(dp, None))
    return jax.jit(api.decode_step,
                   in_shardings=(params_sh, tok_sh, st_sh),
                   out_shardings=(None, st_sh),
                   donate_argnums=(2,))


def _params_shardings(api: ModelApi, ctx: MeshContext):
    # build from an eval_shape of init (no allocation)
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    if api.cfg.parallel.serve_param_sharding == "tp":
        # inference layout: TP only -- no FSDP all-gathers per step
        import dataclasses as _dc
        ctx = MeshContext(mesh=ctx.mesh,
                          parallel=_dc.replace(ctx.parallel, fsdp=False))
    specs = param_specs(shapes, ctx)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class ServeSession:
    """Greedy batched decoding session (single-host friendly).

    Prefill and decode are staged through cached jitted step functions --
    one compilation per batch size, shared across every ``generate`` call
    of the session (the compiled-function cache is keyed on the batch
    size; ``max_seq`` is fixed per session).  Inside an active
    :func:`repro.distributed.sharding.mesh_context` the session uses the
    sharded :func:`jit_prefill` / :func:`jit_decode_step` wrappers
    (KV-cache shardings, donated state); outside one it falls back to
    plain ``jax.jit`` of the model api.
    """
    api: ModelApi
    params: Any
    max_seq: int = 128
    _compiled: dict = dataclasses.field(default_factory=dict, repr=False)

    def _fns(self, batch: int):
        """(prefill_fn, decode_fn) for this batch size, compiled once.

        Keyed on the active mesh context too: a session used both inside
        and outside ``mesh_context`` (or across different meshes) must not
        reuse functions compiled for the wrong sharding.
        """
        from ..distributed.sharding import current_ctx
        ctx = current_ctx()
        key = (batch, None) if ctx is None else \
            (batch, ctx.mesh, ctx.parallel)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit
        if ctx is not None:
            template = jax.eval_shape(
                lambda: self.api.init_decode_state(batch, self.max_seq))
            fns = (jit_prefill(self.api, ctx, template),
                   jit_decode_step(self.api, ctx, template))
        else:
            fns = (jax.jit(self.api.prefill, donate_argnums=(2,)),
                   jax.jit(self.api.decode_step, donate_argnums=(2,)))
        self._compiled[key] = fns
        return fns

    def prefill(self, prompts: jax.Array) -> tuple[jax.Array, Any]:
        """prompts: [B, S] int32 -> (last-position logits, decode state)."""
        b = prompts.shape[0]
        state = self.api.init_decode_state(b, self.max_seq)
        return self._fns(b)[0](self.params, prompts, state)

    def generate(self, prompts: jax.Array, steps: int) -> jax.Array:
        """prompts: [B, S] int32 -> generated tokens [B, steps]."""
        decode = self._fns(prompts.shape[0])[1]
        logits, state = self.prefill(prompts)
        outs = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for _ in range(steps):
            outs.append(tok)
            logits, state = decode(self.params, tok, state)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.stack(outs, axis=1)
