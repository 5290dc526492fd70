"""The main path's device programs compile for a TPU v5e at real widths.

Nothing runs: each program is lowered from shapes placed on one chip of a
described ``v5e:2x2`` topology and compiled by the TPU compiler installed
with jax, which refuses what the chip would refuse (block shapes off the
(8, 128) tiling, VMEM overflow, unsupported ops) -- faults interpret mode
cannot show.  The topology is described inside a fixture, never at import:
only one process may load the TPU library, and the test workers each
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import DESIGNS, fastsim, sweep_workload
from repro.kernels import SCHEDULES, flash_mha, rasa_matmul, ssd_chunk_fused
from repro.workload.compile import CompileOptions, compile_workload


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("m", [128, 2048])
def test_rasa_gemm_compiles(one_chip, m, schedule):
    """qwen3-1.7b's FFN down-projection widths (K=6144 -> 12 k-chunks for
    the weight-stationary schedules), decode-ish and prefill M."""
    a = jax.ShapeDtypeStruct((m, 2048), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((2048, 6144), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(lambda x, y: rasa_matmul(x, y, schedule=schedule,
                                            interpret=False), a, b)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    """qwen3-1.7b heads: 16 query / 8 kv heads of width 128, 2k prompt."""
    q = jax.ShapeDtypeStruct((1, 16, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16,
                              sharding=one_chip)
    hlo = _compile(lambda q_, k_, v_: flash_mha(q_, k_, v_, interpret=False),
                   q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_ssd_chunk_compiles(one_chip):
    """mamba2-130m: 24 heads of width 64, state 128, chunk 256."""
    bh, s, p, n = 24, 2048, 64, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _compile(lambda x, dt, a, b, c: ssd_chunk_fused(x, dt, a, b, c,
                                                          chunk=256),
                   sds((bh, s, p), jnp.bfloat16), sds((bh, s), jnp.float32),
                   sds((bh,), jnp.float32), sds((bh, s, n), jnp.bfloat16),
                   sds((bh, s, n), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_fastsim_scan_compiles(one_chip, monkeypatch):
    """The float64 design-sweep scan (the MM-only port path that
    ``sweep_workload`` takes) compiles for the chip under scoped x64.  Its
    argument shapes are taken from a small CPU sweep; the chunk length and
    design batch are the same at every width."""
    calls = []
    real = fastsim._jax_mm_fn()

    def record(*args):
        calls.append(args)
        return real(*args)

    monkeypatch.setattr(fastsim, "_jax_mm_fn", lambda: record)
    specs = compile_workload("qwen3-1.7b", batch=1, seq=32, phase="prefill",
                             options=CompileOptions(dim_cap=128,
                                                    max_layers=1)).specs
    sweep_workload(list(specs), list(DESIGNS), backend="jax")
    assert calls
    with fastsim.x64():
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                           sharding=one_chip), calls[0])
        lowered = real.lower(*args)
        lowered.compile()
    # the program is float64 (the chip emulates it), not a float32 rewrite
    assert "f64" in lowered.as_text()
