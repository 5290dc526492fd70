"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; same code path compiles for TPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (GemmBlocks, SCHEDULES, flash_mha, rasa_matmul,
                           schedule_cost, default_blocks)
from repro.kernels.ref import (ref_attention, ref_decode_attention,
                               ref_matmul, ref_matmul_accum)

SMALL = GemmBlocks(128, 128, 128)


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(np.abs(want).max(), 1e-6)
    return np.abs(got - want).max() / scale


# ------------------------------------------------------------------ rasa_gemm
@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 256),
                                   (257, 130, 100), (64, 512, 64),
                                   (1, 256, 256)])
def test_gemm_shapes(schedule, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash((schedule,) + shape) % 2**32)
    a = rng.normal(size=(m, k)).astype(jnp.bfloat16)
    b = rng.normal(size=(k, n)).astype(jnp.bfloat16)
    got = rasa_matmul(a, b, schedule=schedule, blocks=SMALL)
    assert rel_err(got, ref_matmul(a, b)) < 1e-5


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gemm_dtypes(schedule, dtype):
    rng = np.random.default_rng(7)
    a = rng.normal(size=(130, 260)).astype(dtype)
    b = rng.normal(size=(260, 140)).astype(dtype)
    got = rasa_matmul(a, b, schedule=schedule, blocks=SMALL)
    assert got.dtype == jnp.float32
    assert rel_err(got, ref_matmul(a, b)) < 1e-5


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_gemm_accumulates_into_c(schedule):
    rng = np.random.default_rng(3)
    a = rng.normal(size=(128, 256)).astype(jnp.bfloat16)
    b = rng.normal(size=(256, 128)).astype(jnp.bfloat16)
    c = rng.normal(size=(128, 128)).astype(np.float32)
    got = rasa_matmul(a, b, c, schedule=schedule, blocks=SMALL)
    assert rel_err(got, ref_matmul_accum(a, b, c)) < 1e-5


def test_gemm_schedules_bit_identical():
    """All three schedules perform the same fp32 k-order reduction."""
    rng = np.random.default_rng(11)
    a = rng.normal(size=(256, 512)).astype(jnp.bfloat16)
    b = rng.normal(size=(512, 256)).astype(jnp.bfloat16)
    outs = [np.asarray(rasa_matmul(a, b, schedule=s, blocks=SMALL))
            for s in SCHEDULES]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_default_blocks_fit_budget():
    for shape in [(8192, 8192, 8192), (128, 128, 128), (100000, 64, 64)]:
        blocks = default_blocks(*shape)
        assert 2 * blocks.vmem_bytes() <= 8 * 2**20
        assert blocks.bm % 128 == 0 or blocks.bm == min(128, shape[0])


def test_schedule_cost_model():
    """wlbp must beat base on B traffic for tall GEMMs (the WL skip), and
    wls minimizes C traffic (output-stationary)."""
    m, k, n = 8192, 4096, 4096
    blocks = GemmBlocks(256, 512, 256)
    base = schedule_cost(m, k, n, blocks, "base")
    wlbp = schedule_cost(m, k, n, blocks, "wlbp")
    wls = schedule_cost(m, k, n, blocks, "wls")
    assert wlbp["traffic_bytes"]["B"] < base["traffic_bytes"]["B"]
    assert wls["traffic_bytes"]["C"] < base["traffic_bytes"]["C"]
    assert wls["arithmetic_intensity"] > base["arithmetic_intensity"]


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 300), st.integers(1, 300), st.integers(1, 300),
       st.sampled_from(SCHEDULES))
def test_gemm_property(m, k, n, schedule):
    rng = np.random.default_rng(m * 7 + k * 3 + n)
    a = rng.normal(size=(m, k)).astype(jnp.bfloat16)
    b = rng.normal(size=(k, n)).astype(jnp.bfloat16)
    got = rasa_matmul(a, b, schedule=schedule, blocks=SMALL)
    assert rel_err(got, ref_matmul(a, b)) < 1e-5


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("sq", [128, 257, 384])
def test_flash_attention_causal(hq, hkv, sq):
    rng = np.random.default_rng(sq * hq)
    q = rng.normal(size=(2, hq, sq, 64)).astype(jnp.bfloat16)
    k = rng.normal(size=(2, hkv, sq, 64)).astype(jnp.bfloat16)
    v = rng.normal(size=(2, hkv, sq, 64)).astype(jnp.bfloat16)
    got = flash_mha(q, k, v, block_q=128, block_kv=128)
    want = ref_attention(q, k, v)
    assert rel_err(got, want) < 2e-2      # bf16 inputs/outputs


def test_flash_attention_fp32_tight():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(1, 2, 256, 128)).astype(np.float32)
    k = rng.normal(size=(1, 2, 256, 128)).astype(np.float32)
    v = rng.normal(size=(1, 2, 256, 128)).astype(np.float32)
    got = flash_mha(q, k, v, block_q=128, block_kv=128)
    assert rel_err(got, ref_attention(q, k, v)) < 1e-5


def test_flash_attention_matches_scale():
    rng = np.random.default_rng(1)
    q = rng.normal(size=(1, 2, 128, 64)).astype(np.float32)
    k = rng.normal(size=(1, 2, 128, 64)).astype(np.float32)
    v = rng.normal(size=(1, 2, 128, 64)).astype(np.float32)
    got = flash_mha(q, k, v, scale=0.5, block_q=128, block_kv=128)
    want = ref_attention(q, k, v, scale=0.5)
    assert rel_err(got, want) < 1e-5


def test_decode_attention_ref_consistency():
    """ref_decode_attention == ref_attention's last position."""
    rng = np.random.default_rng(5)
    s = 64
    q = rng.normal(size=(2, 8, 1, 32)).astype(np.float32)
    k = rng.normal(size=(2, 2, s, 32)).astype(np.float32)
    v = rng.normal(size=(2, 2, s, 32)).astype(np.float32)
    full = ref_attention(q, k, v, causal=False)
    dec = ref_decode_attention(q[:, :, 0], k, v)
    np.testing.assert_allclose(np.asarray(full[:, :, 0]), np.asarray(dec),
                               rtol=1e-5, atol=1e-5)


def test_interpret_only_on_cpu(monkeypatch):
    """Left to choose, the kernels interpret on the CPU and compile on the
    TPU; any other backend is refused rather than silently interpreted."""
    from repro.kernels import ops
    assert ops._interpret_default() is True          # this suite: CPU
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        ops._interpret_default()
