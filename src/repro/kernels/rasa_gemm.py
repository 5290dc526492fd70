"""RASA-scheduled tiled GEMM as a Pallas TPU kernel.

This is the TPU adaptation of the paper's matrix engine (DESIGN.md §3).
The MXU *is* a weight-stationary systolic array; what RASA controls on a
CPU -- when the stationary operand is (re)loaded and how consecutive
``rasa_mm`` overlap -- is on TPU controlled by the *grid iteration order*
and the Pallas software pipeline:

  schedule="base"  grid (k, m, n), n innermost.  The B block changes on
                   every grid step: the "weight load" (HBM->VMEM copy of B)
                   is paid every time.  This is the BASE design: WL before
                   every rasa_mm.
  schedule="wlbp"  grid (k, n, m), m innermost.  For a fixed (k, n) the
                   B block is *revisited*; Pallas elides the copy -- the
                   compile-time analogue of the WLBP dirty-bit skip.  C is
                   streamed in/out per step (the register round-robin).
  schedule="wls"   grid (m, n, k), k innermost with an fp32 VMEM scratch
                   accumulator.  B blocks stream, but every copy is
                   prefetched by the double-buffered pipeline during the
                   previous step's compute -- the DB-WLS shadow-buffer
                   schedule.  Output-stationary: C written once.

Block sizes (bm, bk, bn) are the "tile register" dims; on TPU they are
bounded by VMEM instead of eight 1 KB registers, and must be multiples of
the MXU/VREG tiling (128 lanes; 16 sublanes for bf16).  The `dm` analogue
(two MACs per PE with a merge) corresponds to doubling bk at half the bm
grid -- exposed simply as block-shape tuning here.

The `schedule_cost` model mirrors core/timing.py at the DMA level and is
used by the perf loop for napkin math before each change.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SCHEDULES = ("base", "wlbp", "wls")


@dataclasses.dataclass(frozen=True)
class GemmBlocks:
    bm: int = 256
    bk: int = 512
    bn: int = 256

    def vmem_bytes(self, in_dtype_bytes: int = 2) -> int:
        """Working set per pipeline stage (x2 when double buffered)."""
        return (self.bm * self.bk * in_dtype_bytes
                + self.bk * self.bn * in_dtype_bytes
                + self.bm * self.bn * 4)


# --------------------------------------------------------------------------
# kernel bodies
# --------------------------------------------------------------------------

def _accum_kernel(c_in_ref, a_ref, b_ref, o_ref):
    """C-streaming body (base / wlbp): o = c_in + a @ b.

    Each pallas_call covers ONE k-chunk (the T_K reduction that maps onto
    the array in a single rasa_mm); chaining across k-chunks happens at the
    JAX level through the C buffer -- the analogue of streaming partial
    sums through the C tile register between rasa_mm instructions.  Cross-
    grid-step accumulation through aliased HBM is deliberately avoided: it
    would race with the double-buffered pipeline on real hardware.
    """
    o_ref[...] = (c_in_ref[...].astype(jnp.float32)
                  + jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)
                  ).astype(o_ref.dtype)


def _scratch_kernel(a_ref, b_ref, c_in_ref, o_ref, acc_ref, *, k_axis: int):
    """Output-stationary body (wls): accumulate in VMEM scratch; write once."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_in_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(k_axis) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call assembly
# --------------------------------------------------------------------------

def _ws_call(a: jax.Array, b: jax.Array, c: jax.Array, schedule: str,
             blocks: GemmBlocks, out_dtype, interpret: bool) -> jax.Array:
    """One weight-stationary pallas_call over a single k-chunk.

    base: grid (m, n) with n innermost -- the B block changes every step
          (WL paid per rasa_mm).
    wlbp: grid (n, m) with m innermost -- the B block is revisited across
          the whole m sweep; Pallas elides the copy (the WL skip).
    """
    m, k = a.shape
    n = b.shape[1]
    bm, bk, bn = blocks.bm, blocks.bk, blocks.bn
    assert k == bk, "one WS call covers exactly one k-chunk"
    mt, nt = m // bm, n // bn
    if schedule == "base":
        grid = (mt, nt)
        a_spec = pl.BlockSpec((bm, bk), lambda i, j: (i, 0))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j: (0, j))
        c_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    else:  # wlbp
        grid = (nt, mt)
        a_spec = pl.BlockSpec((bm, bk), lambda j, i: (i, 0))
        b_spec = pl.BlockSpec((bk, bn), lambda j, i: (0, j))
        c_spec = pl.BlockSpec((bm, bn), lambda j, i: (i, j))
    return pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[c_spec, a_spec, b_spec],
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(c, a, b)


def rasa_gemm(a: jax.Array, b: jax.Array, c: jax.Array | None = None,
              *, schedule: str = "wls", blocks: GemmBlocks | None = None,
              out_dtype: jnp.dtype = jnp.float32,
              interpret: bool = False) -> jax.Array:
    """C (+)= A @ B with a RASA-scheduled Pallas kernel.

    a: [M, K], b: [K, N], optional c: [M, N] accumulator input.
    Shapes must be multiples of the block dims (ops.py pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    blocks = blocks or default_blocks(m, k, n)
    bm, bk, bn = blocks.bm, blocks.bk, blocks.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (f"shape ({m},{k},{n}) not divisible by blocks {blocks}; "
         f"use ops.rasa_matmul which pads")
    mt, nt, kt = m // bm, n // bn, k // bk
    if c is None:
        c = jnp.zeros((m, n), out_dtype)
    else:
        c = c.astype(out_dtype)

    if schedule == "wls":
        # output-stationary fused reduction: grid (m, n, k), k innermost,
        # fp32 scratch accumulator, C written exactly once.
        grid = (mt, nt, kt)
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
        c_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        return pl.pallas_call(
            functools.partial(_scratch_kernel, k_axis=2),
            grid=grid,
            in_specs=[a_spec, b_spec, c_spec],
            out_specs=c_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(a, b, c)

    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; one of {SCHEDULES}")

    # base / wlbp: weight-stationary; k-chunks chained through the C buffer
    # (the C tile-register stream), one pallas_call per chunk.
    out = c
    for kk in range(kt):
        out = _ws_call(a[:, kk * bk:(kk + 1) * bk],
                       b[kk * bk:(kk + 1) * bk, :],
                       out, schedule, blocks, out_dtype, interpret)
    return out


def default_blocks(m: int, k: int, n: int,
                   vmem_budget_bytes: int = 8 * 2**20) -> GemmBlocks:
    """Pick MXU-aligned blocks that fit the (double-buffered) VMEM budget."""
    def shrink(x, b):
        while b > 128 and x % b != 0:
            b //= 2
        return min(b, max(128, x))
    bm = shrink(m, 256)
    bk = shrink(k, 512)
    bn = shrink(n, 256)
    blocks = GemmBlocks(bm, bk, bn)
    while 2 * blocks.vmem_bytes() > vmem_budget_bytes and blocks.bk > 128:
        blocks = GemmBlocks(blocks.bm, blocks.bk // 2, blocks.bn)
    return blocks


# --------------------------------------------------------------------------
# DMA cost model (napkin math for the perf loop; mirrors core/timing.py)
# --------------------------------------------------------------------------

def schedule_cost(m: int, k: int, n: int, blocks: GemmBlocks,
                  schedule: str, in_bytes: int = 2, out_bytes: int = 4) -> dict:
    """Bytes moved HBM<->VMEM per schedule (the kernel-level roofline)."""
    mt, kt, nt = m // blocks.bm, k // blocks.bk, n // blocks.bn
    a_bytes = m * k * in_bytes
    b_bytes = k * n * in_bytes
    c_bytes = m * n * out_bytes
    if schedule == "base":
        # (k, m, n): A elided across n-inner; B refetched every step ("WL
        # before every rasa_mm"); C streamed in+out on every k pass.
        traffic = {"A": a_bytes, "B": b_bytes * mt, "C": 2 * c_bytes * kt}
    elif schedule == "wlbp":
        # (k, n, m): B elided across m-inner (the WL skip); A refetched per n.
        traffic = {"A": a_bytes * nt, "B": b_bytes, "C": 2 * c_bytes * kt}
    else:  # wls: (m, n, k) output-stationary, C written once
        traffic = {"A": a_bytes * nt, "B": b_bytes * mt, "C": 2 * c_bytes}
    total = sum(traffic.values())
    flops = 2 * m * k * n
    return {"schedule": schedule, "traffic_bytes": traffic,
            "total_bytes": total, "flops": flops,
            "arithmetic_intensity": flops / total}
