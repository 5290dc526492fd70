"""Whole-trace jitted span arbitration: an online serving run as ONE XLA
program.

The incremental client (:class:`repro.multicore.online.OnlineChip`) walks
an arrival trace on the host: every start boundary marks the in-flight
spans dirty, relaxes the share fixed point, and re-simulates dirty
segments -- with ``backend="jax"`` one batched scan per relaxation round.
For very long traces the remaining host work (the boundary event loop and
one device dispatch per round) dominates.  This module lowers that *whole
loop* into a single ``lax.while_loop`` program:

* the arbiter's **settled-prefix cache is a carried array**: ``wsum[e]``
  holds the per-epoch active-weight sums over a *sliding window* of
  ``2 * S`` epochs anchored at ``max(0, boundary - S)`` -- every read and
  write a settle can make lands within ``S`` epochs of its boundary (the
  same span bound that sizes the share window), so the window slides
  forward monotonically, settled epochs spill off the left edge as
  immutable facts, and the carried state is O(S) regardless of trace
  length (100k-1M-request traces fit without an O(horizon) array);
* **retired spans are masked, not pruned**: each core lane carries only
  its *current* segment (a replaced segment's end always precedes every
  later boundary, so it is a settled fact -- the same causality argument
  the host client's retirement rests on), and its contribution lives on
  in the carried prefix;
* per-epoch weight sums are folded in the **host arbiter's span order**
  (start epoch, then core index -- the order ``_pump`` appends spans),
  one masked add per lane, so demand-weighted float weights accumulate
  in exactly the order ``SpanArbiter._rebuild``'s fresh per-epoch fold
  uses and grants stay bit-identical (equal shares reduce to the old
  integer counts, exact in any order);
* the host client's **snapshot cache is a carried array too**: every
  relaxation re-sim records the 15-slot timing carry at each
  ``_BLOCK``-instruction boundary, and later rounds resume from the
  deepest snapshot whose ``last_grant`` precedes the dirty boundary.
  Such a carry is fully determined by grants in the settled prefix
  (``bt <= last_grant`` is a step invariant, and engine-side pipeline
  state depends on the schedule only through grant times), so resuming
  from it is bit-exact -- and each round costs the dirty *suffix*, not
  the whole trace;
* **designs are per-lane data**: the simulate chunk is vmapped with the
  engine scalars and port rates on the lane axis, so heterogeneous core
  mixes (BASE cores next to RASA cores, per-core tiling policies) jit in
  the same executable -- and changing the design never recompiles;
* **admission runs inside the loop**: the serving batcher's reactive
  policies (``occupancy``/``bandwidth``/``predicted``) are replayed as
  carried scalars -- the program interleaves start boundaries with the
  host driver's decision epochs (next arrival, or the chip's next event
  while requests wait), recomputes headroom/occupancy/soonest-free
  placement from the *settled* carried state exactly as the host queries
  it, and records admit epochs -- no host round-trip per batch.  The
  ``fixed`` policy (any ``batch_size``) needs no in-program decisions at
  all: its flush epochs are a closed form of the arrival order, so the
  queues enter fully precomputed.

The outer ``while_loop`` replays the boundary event loop (per-core
candidate = max(queue-head submit epoch, core-free epoch); all cores
sharing the minimal boundary start together), and an inner ``while_loop``
runs the relaxation rounds, each round re-simulating the non-settled
lanes with a block-chunked vmapped
:func:`repro.core.fastsim._sim_chunk_fn` scan.

**Domain.**  The program covers the serving batcher's ``fixed`` (any
batch size), ``occupancy``, ``bandwidth`` and ``predicted`` admission
policies, equal or demand-weighted shares (any ``SharePolicy``: weights
are host-measured per (request shape, core) with the client's own
unthrottled probe), homogeneous or mixed fault-free chips.
:func:`plan_ex` returns a structured gate reason outside the domain (see
``GATE_REASONS``) and callers fall back to the incremental client;
inside it, results are **bit-identical** to the numpy oracle (pinned by
``tests/test_online_jax.py`` and asserted at scale by
``benchmarks/online_scaling.py``):

* the per-instruction scan is the shared ``sim_chunk`` program (bit-exact
  with the numpy token bucket);
* every share is the same expression numpy evaluates
  (``budget * w / wsum[e]``, tails ``budget * w / w_forever`` open and
  ``budget`` closed), weight sums fold in the host's span order, and with
  the power-of-two ``epoch_cycles`` all boundary arithmetic
  (``floor(last_grant / E)``, ``ceil(finish / E)``) is exact;
* admission queries are the host's own expressions: headroom counts
  ``budget / (n_active + k + 1) >= min_share`` terms, ``free_at``
  estimates fold the same per-core cost table in queue order, placement
  ties break on the lowest core index exactly like the host's
  first-minimal ``min``/stable sort;
* skip rules only avoid re-simulating values that could not change
  (settled spans are frozen, resumes replay the settled prefix's exact
  state), so the program walks the *same* end-estimate trajectory to the
  same fixed point as the host relaxation.

Since everything dynamic enters as arrays, arrival traces ``vmap``: an
arrival-rate sweep runs as one device launch (:func:`finish_times_many`,
demonstrated by ``benchmarks/serving_batch.py``).  Shapes are padded to
power-of-two grids (requests, trace rows, queue depth, share window), so
repeated calls with different trace lengths reuse one executable.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import numpy as np

from ..core.fastsim import _design_arrays, _pow2, run_segment, x64
from ..core.isa import NUM_TREGS
from ..core.tiling import GemmSpec
from ..core.trace import OP_NOP, CompiledTrace, compiled_trace
from .arbiter import MAX_ARBITER_ROUNDS
from .chip import ChipConfig, demands_bandwidth, shared_traffic_bytes, \
    stream_model_params

__all__ = ["plan", "plan_ex", "plan_many", "finish_times",
           "finish_admit_times", "finish_times_many", "Plan",
           "GATE_REASONS"]

#: snapshot granularity of the in-program resume cache (instructions per
#: simulated block); trace columns are padded to a multiple of this
_BLOCK = 64

#: admission policies the program replays in-loop (``fixed`` needs no
#: in-loop decisions; the reactive three do)
MODES = ("fixed", "occupancy", "bandwidth", "predicted")

#: cap on the statically-unrolled admissions per decision epoch (the
#: headroom bound ``floor(budget / min_share)``); configs beyond it gate
_KMAX_CAP = 64

#: every reason :func:`plan_ex` can return (the ``BatchReport.jit_gate``
#: vocabulary); ``None`` means the trace jitted
GATE_REASONS = (
    "no_requests",          # empty trace: nothing to settle
    "backend",              # chip.backend != "jax"
    "arbitration",          # only the epoch arbiter is lowered
    "faults_active",        # fault plans replay host-side only
    "admission_policy",     # policy outside MODES (phase_aware, ...)
    "batch_size",           # fixed admission needs batch_size >= 1
    "lookahead",            # predicted admission needs lookahead >= 0
    "epoch_not_pow2",       # exact t/E arithmetic needs 2**k epochs
    "infinite_budget",      # unthrottled chips have no share schedule
    "min_share_out_of_range",  # reactive headroom needs 0 < ms <= budget
    "admission_unroll",     # floor(budget/min_share) > _KMAX_CAP
    "hetero_store_model",   # cores disagree on store-byte charging
    "zero_traffic_segment",  # a request shape with no shared traffic
)


# --------------------------------------------------------------------------
# host-side planning
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Plan:
    """Host-precomputed arrays for one (or many) kernel launches.

    Everything the kernel needs that depends only on the *chip and the
    request shapes* is shared; the per-trace arrays (arrivals, queue
    prefill, shape ids) are what an arrival-rate sweep maps over.  All
    shapes are padded to power-of-two grids so the jitted executable is
    keyed by the grid, not the trace.
    """

    chip: ChipConfig
    cols: tuple                 # 7 stacked trace columns, each [R, L]
    tr_len: np.ndarray          # [R] i32 true (unpadded) trace lengths
    t2l: np.ndarray             # [U, C] i32 trace row per (shape, core)
    wt: np.ndarray              # [U, C] f64 span weight per (shape, core)
    est: np.ndarray             # [U, C] f64 unthrottled cycle estimates
    arrival: np.ndarray         # [N] f64 arrival epochs (sorted; pads inf)
    qidx: np.ndarray            # [C, maxq] i32 queue prefill (fixed mode)
    qsub: np.ndarray            # [C, maxq] f64 submit epochs (fixed mode)
    qtail0: np.ndarray          # [C] i32 initial queue fill (fixed mode)
    tid_of: np.ndarray          # [N] i32 shape id per sorted rank (pads 0)
    order: np.ndarray           # [n_real] caller index per sorted rank
    adm_fixed: np.ndarray | None  # [n_real] fixed-mode admit epochs
    mode: str                   # one of MODES
    S: int                      # share-window epochs (>= max span length)
    maxq: int
    kmax: int                   # per-decision admission unroll
    min_share: float
    lookahead: int
    n_real: int                 # true request count (<= len(arrival))


def _stack_cols(traces: Sequence[CompiledTrace], length: int) -> tuple:
    padded = [t.padded(length) for t in traces]
    return tuple(
        np.stack([(tr.opcode, tr.r_dst, tr.r_a, tr.r_b, tr.nbytes, tr.tm,
                   tr.reusable)[f] for tr in padded])
        for f in range(7))


def _nop_rows(cols: tuple, tr_len: np.ndarray, rows: int
              ) -> tuple[tuple, np.ndarray]:
    """Pad the trace table to ``rows`` with zero-length NOP rows."""
    r, length = cols[0].shape
    if r >= rows:
        return cols, tr_len
    out = []
    for f, c in enumerate(cols):
        pad = np.full((rows - r, length), OP_NOP if f == 0 else 0,
                      dtype=c.dtype)
        out.append(np.concatenate([c, pad], axis=0))
    return tuple(out), np.concatenate(
        [tr_len, np.zeros(rows - r, dtype=np.int32)])


def plan(traffic: Sequence[tuple[int, Sequence[GemmSpec]]],
         chip: ChipConfig, *, policy: str = "fixed", batch_size: int = 1,
         min_share: float | None = None, lookahead: int = 1
         ) -> Plan | None:
    """:func:`plan_ex` without the gate reason (legacy call shape)."""
    return plan_ex(traffic, chip, policy=policy, batch_size=batch_size,
                   min_share=min_share, lookahead=lookahead)[0]


def plan_ex(traffic: Sequence[tuple[int, Sequence[GemmSpec]]],
            chip: ChipConfig, *, policy: str = "fixed",
            batch_size: int = 1, min_share: float | None = None,
            lookahead: int = 1) -> tuple[Plan | None, str | None]:
    """Precompute the kernel inputs for one arrival trace.

    ``traffic`` is ``(arrival_epoch, specs)`` per request, in caller
    order.  Returns ``(Plan, None)`` inside the jitted program's domain
    and ``(None, reason)`` outside it -- the caller then uses the
    incremental client and can surface the reason (see ``GATE_REASONS``);
    raising here would turn a routing decision into an error.
    """
    if not traffic:
        return None, "no_requests"
    if chip.backend != "jax":
        return None, "backend"
    if chip.arbitration != "epoch":
        return None, "arbitration"
    if chip.fault_plan is not None and not chip.fault_plan.is_empty:
        return None, "faults_active"
    if policy not in MODES:
        return None, "admission_policy"
    if policy == "fixed" and batch_size < 1:
        return None, "batch_size"
    if policy == "predicted" and lookahead < 0:
        return None, "lookahead"
    E = chip.epoch_cycles
    if not (math.isfinite(E) and E > 0
            and math.log2(E).is_integer()):
        return None, "epoch_not_pow2"
    budget = chip.bw_bytes_per_cycle
    if not math.isfinite(budget):
        return None, "infinite_budget"

    C = chip.n_cores
    N = len(traffic)
    reactive = policy != "fixed"
    if min_share is None:
        min_share = budget / (2.0 * C)
    if reactive and not (0.0 < min_share <= budget):
        return None, "min_share_out_of_range"
    kmax_true = int(budget / min_share) if reactive else 1
    if reactive and min(N, kmax_true) > _KMAX_CAP:
        return None, "admission_unroll"
    params = [stream_model_params(chip, cs.engine)
              for cs in chip.core_specs]
    if len({pp.store_ports is None for pp in params}) != 1:
        # the chunk treats store-byte charging as static: a chip whose
        # engines disagree on it cannot share one program
        return None, "hetero_store_model"

    # trace rows are per (request shape, tiling policy): cores sharing a
    # policy share rows, a mixed chip gets one row per distinct policy
    pgroups: list = []
    pgroup_of = np.zeros(C, dtype=np.int32)
    for c, cs in enumerate(chip.core_specs):
        for gi, g in enumerate(pgroups):
            if g == cs.policy:
                pgroup_of[c] = gi
                break
        else:
            pgroup_of[c] = len(pgroups)
            pgroups.append(cs.policy)

    order_in = sorted(range(N), key=lambda i: traffic[i][0])
    keys: dict[tuple, int] = {}
    shapes: list[tuple] = []
    tid_of = np.zeros(N, dtype=np.int32)
    arrival = np.zeros(N, dtype=np.float64)
    for r, i in enumerate(order_in):
        ep, specs = traffic[i]
        key = tuple(dataclasses.replace(s, name="") for s in specs)
        u = keys.get(key)
        if u is None:
            u = keys[key] = len(shapes)
            shapes.append(key)
        tid_of[r] = u
        arrival[r] = float(ep)

    rows: dict[tuple[int, int], int] = {}
    traces: list[CompiledTrace] = []
    U = len(shapes)
    t2l = np.zeros((U, C), dtype=np.int32)
    for u, key in enumerate(shapes):
        for c in range(C):
            gi = int(pgroup_of[c])
            t = rows.get((u, gi))
            if t is None:
                t = rows[(u, gi)] = len(traces)
                traces.append(compiled_trace(key, pgroups[gi]))
            t2l[u, c] = t
    for tr in traces:
        if len(tr) == 0 or not demands_bandwidth(chip, None, tr):
            return None, "zero_traffic_segment"

    # span weights: the host client measures each admitted segment's
    # unthrottled demand on its core and maps it through the share
    # policy; weight is a pure function of (shape, core), so the probe
    # runs once per table cell and enters the kernel as data
    share_policy = chip.share_policy
    wt = np.ones((U, C), dtype=np.float64)
    if getattr(share_policy, "needs_demand", False):
        cache: dict[tuple, float] = {}
        for u in range(U):
            for c in range(C):
                engine = chip.core_specs[c].engine
                ck = (int(t2l[u, c]), engine)
                d = cache.get(ck)
                if d is None:
                    tr = traces[t2l[u, c]]
                    res, _, _ = run_segment(
                        tr, engine, stream_model_params(chip, engine))
                    traffic_b = shared_traffic_bytes(chip, None, tr)
                    d = cache[ck] = \
                        traffic_b / res.cycles if res.cycles else 0.0
                wt[u, c] = share_policy.weight(d)

    # queued-cost estimates (free_at placement): the host's own cached
    # per-(spec, core-design) estimator, summed per request shape
    est = np.zeros((U, C), dtype=np.float64)
    if reactive:
        from .scheduler import _estimate_cycles
        for u, key in enumerate(shapes):
            for c in range(C):
                est[u, c] = float(sum(_estimate_cycles(s, chip, c)
                                      for s in key))

    # sound per-segment span bound: at most one span per core is active,
    # each weighing at most its core's table max, so every relaxed share
    # is >= budget * w / wf_max -- a segment's epoch count under any
    # reachable schedule is bounded by its constant-floor-share run
    wf_max = float(np.sum(np.max(wt, axis=0)))
    l_max = 0
    lcache: dict[tuple, int] = {}
    for u in range(U):
        for c in range(C):
            engine = chip.core_specs[c].engine
            floor = budget * wt[u, c] / wf_max
            ck = (int(t2l[u, c]), engine, floor)
            n = lcache.get(ck)
            if n is None:
                res, _, _ = run_segment(
                    traces[t2l[u, c]], engine,
                    stream_model_params(chip, engine, (), E, floor))
                n = lcache[ck] = int(res.cycles // E) + 2
            l_max = max(l_max, n)

    # an open span's visible prefix can reach the horizon set by another
    # lane, at most ~2 span lengths past its own start (see module docs)
    S = _pow2(2 * l_max + 4, lo=8)

    # pad every dynamic extent to a power-of-two grid: the executable is
    # keyed by the grid, so nearby trace sizes share one compilation
    Np = _pow2(N, lo=8)
    arrival_p = np.full(Np, np.inf, dtype=np.float64)
    arrival_p[:N] = arrival
    tid_p = np.zeros(Np, dtype=np.int32)
    tid_p[:N] = tid_of

    adm_fixed = None
    if reactive:
        maxq = Np
        qidx = np.zeros((C, maxq), dtype=np.int32)
        qsub = np.zeros((C, maxq), dtype=np.float64)
        qtail0 = np.zeros(C, dtype=np.int32)
        kmax = _pow2(max(1, min(N, kmax_true)), lo=4)
    else:
        # fixed admission is a closed form of the arrival order: rank r
        # goes to core r % C when group r // batch_size flushes -- at the
        # arrival of the group's last member (the drained partial group
        # flushes with the final arrival)
        qlen = np.zeros(C, dtype=np.int32)
        for r in range(N):
            qlen[r % C] += 1
        maxq = _pow2(int(qlen.max()), lo=1)
        qidx = np.zeros((C, maxq), dtype=np.int32)
        qsub = np.zeros((C, maxq), dtype=np.float64)
        qtail0 = qlen
        adm_fixed = np.zeros(N, dtype=np.float64)
        fill = np.zeros(C, dtype=np.int32)
        for r in range(N):
            g = r // batch_size
            adm_fixed[r] = arrival[min((g + 1) * batch_size - 1, N - 1)]
            c = r % C
            qidx[c, fill[c]] = r
            qsub[c, fill[c]] = adm_fixed[r]
            fill[c] += 1
        kmax = 1

    L = -(-max(len(t) for t in traces) // _BLOCK) * _BLOCK
    L = _pow2(L // _BLOCK, lo=1) * _BLOCK
    cols, tr_len = _nop_rows(
        _stack_cols(traces, L),
        np.asarray([len(t) for t in traces], dtype=np.int32),
        _pow2(len(traces), lo=1))
    Up = _pow2(U, lo=1)
    if Up > U:
        t2l = np.concatenate(
            [t2l, np.zeros((Up - U, C), dtype=np.int32)])
        wt = np.concatenate([wt, np.ones((Up - U, C))])
        est = np.concatenate([est, np.zeros((Up - U, C))])
    return Plan(chip=chip, cols=cols, tr_len=tr_len, t2l=t2l, wt=wt,
                est=est, arrival=arrival_p, qidx=qidx, qsub=qsub,
                qtail0=qtail0, tid_of=tid_p,
                order=np.asarray(order_in, dtype=np.int64),
                adm_fixed=adm_fixed, mode=policy, S=S, maxq=maxq,
                kmax=kmax, min_share=float(min_share),
                lookahead=int(lookahead), n_real=N), None


# --------------------------------------------------------------------------
# the program
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _kernel(C: int, N: int, maxq: int, R: int, U: int, L: int, S: int,
            mode: str, charge_store: bool, store_free: bool, kmax: int,
            max_rounds: int):
    """Build (jit, vmapped-jit) of the whole-trace program for one static
    shape signature.  Everything dynamic -- arrivals, queues, trace
    columns, designs, the budget -- is a traced argument, so same-grid
    launches (an arrival sweep, a re-run, a different engine mix) reuse
    the executable."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..core.fastsim import _sim_chunk_fn

    #: per-lane vmap of the simulate chunk: the engine design tuple and
    #: the port rates ride the lane axis (heterogeneous mixes), shares /
    #: schedule bounds as in fastsim's ``_B_CORES`` cores layout
    _B_LANES = (0, 0, None, 0, None, 0, None, None, 0, 0)
    lane_sim = jax.vmap(_sim_chunk_fn(False, False),
                        in_axes=(0, 0, None, (0,) * 8, _B_LANES))
    INF = jnp.inf
    NB = L // _BLOCK
    W = 2 * S
    reactive = mode != "fixed"
    tree = jax.tree_util.tree_map

    def program(cols, tr_len, t2l, wt, est, arrival, qidx0, qsub0, qtail0,
                tid_of, E, budget, burst, inv_load, inv_store, design,
                min_share, lookahead, n_real, packed=True):
        f64 = jnp.float64
        i32 = jnp.int32
        lanes = jnp.arange(C)

        def fresh_carry():
            z = jnp.zeros((C,), f64)
            return (jnp.zeros((C, NUM_TREGS), f64),
                    jnp.full((C,), -1.0, f64), z, z, z,
                    jnp.zeros((C,), bool), z, z,
                    jnp.zeros((C,), jnp.int32), z, z, z, z,
                    jnp.full((C,), burst, f64), z)

        # In the single-trace kernel snapshots live in ONE flat f64 buffer
        # [C, NB+1, D]: a packed 15-element carry per block boundary, so
        # one concatenate + one scatter per simulated block replaces 15 of
        # each -- on CPU the per-block dispatch cost is what the resume
        # cache trades against.  bool/int32 fields roundtrip through f64
        # exactly.  The vmapped kernel keeps the 15-array tuple form:
        # batched scatters into one wide buffer lower to a slower generic
        # scatter than the per-field updates do.
        LG = NUM_TREGS + 11         # packed column of ``last_grant``

        def pack(cy):
            return jnp.concatenate(
                [cy[0]] + [(c if c.dtype == jnp.float64
                            else c.astype(f64))[:, None] for c in cy[1:]],
                axis=1)

        def unpack(p):
            Rg = NUM_TREGS

            def at(i):
                return p[:, Rg + i]

            return (p[:, :Rg], at(0), at(1), at(2), at(3), at(4) != 0.0,
                    at(5), at(6), at(7).astype(jnp.int32), at(8), at(9),
                    at(10), at(11), at(12), at(13))

        def blank_snaps():
            # snapshot slot k of lane l = the carry before block k; slot 0
            # is the fresh segment state, deeper slots start invalid (an
            # inf last_grant never precedes a dirty boundary)
            if packed:
                snaps = jnp.repeat(pack(fresh_carry())[:, None, :],
                                   NB + 1, axis=1)
                return snaps.at[:, 1:, LG].set(INF)
            snaps = tree(
                lambda a: jnp.repeat(a[:, None, ...], NB + 1, axis=1),
                fresh_carry())
            return snaps[:12] + (snaps[12].at[:, 1:].set(INF),) + snaps[13:]

        def reset_snaps(snaps, starts):
            if packed:
                return jnp.where(starts[:, None, None], blank_snaps(),
                                 snaps)
            return tree(
                lambda a, blank: jnp.where(
                    starts[:, None, None] if a.ndim == 3
                    else starts[:, None], blank, a),
                snaps, blank_snaps())

        def snap_lg(snaps):
            return snaps[:, :, LG] if packed else snaps[12]

        def snap_read(snaps, k0):
            if packed:
                return unpack(snaps[lanes, k0])
            return tree(lambda a: a[lanes, k0], snaps)

        def snap_write(snaps, b, act, carry):
            if packed:
                return snaps.at[:, b + 1].set(
                    jnp.where(act[:, None], pack(carry), snaps[:, b + 1]))
            return tree(
                lambda s, c: s.at[:, b + 1].set(
                    jnp.where(act[:, None] if c.ndim == 2 else act,
                              c, s[:, b + 1])),
                snaps, carry)

        def settle(wsum, base, nw, tid, cur, start, ends, lg, te, snaps,
                   d, mxn, p_sh, p_nsh, p_tail):
            """One arbiter settle at boundary ``d``: slide the weight-sum
            window up to ``max(base, d - S)``, zero-fill the idle gap,
            then relax.  Settled epochs spilling off the left edge are
            immutable facts -- no settle reads or writes below
            ``d - S`` (reads span a live span's prefix, writes the
            ``[d, d + S)`` window; both bounded by the span bound S)."""
            base2 = jnp.maximum(base, d - float(S))
            sh = (base2 - base).astype(i32)
            iw = jnp.arange(W, dtype=i32)
            wsum = jnp.where(iw + sh < W,
                             wsum[jnp.clip(iw + sh, 0, W - 1)], 0.0)
            e_abs = base2 + jnp.arange(W, dtype=f64)
            wsum = jnp.where((e_abs >= nw) & (e_abs < d), 0.0, wsum)
            live = tid >= 0
            need = live & jnp.isinf(ends)   # dirty or just-started spans
            tid_s = jnp.maximum(tid, 0)
            row = t2l[tid_s, lanes]
            w_lane = wt[tid_s, lanes]
            lane_cols = tuple(c[row] for c in cols)         # [C, L]
            nblk = (tr_len[row] + (_BLOCK - 1)) // _BLOCK   # [C]
            cutoff = (d - start) * E        # settled-time limit, per lane
            # the host arbiter folds weights over spans in _active order:
            # start epoch, core-index tie-break (the _pump append order)
            perm = jnp.argsort(start * C + lanes.astype(f64))

            def resim(snaps, bucket, sim, fc):
                """Re-simulate the ``sim`` lanes under the current shares.

                A snapshot is reusable when every grant it has absorbed
                lies either in the settled prefix (frozen forever) or
                before the first epoch whose visible share differs from
                the lane's previous sim -- so each lane resumes from its
                deepest such snapshot instead of instruction zero."""
                lim = jnp.maximum(fc * E, cutoff)
                valid = snap_lg(snaps) < lim[:, None]        # [C, NB+1]
                k0 = jnp.max(jnp.where(valid,
                                       jnp.arange(NB + 1, dtype=jnp.int32),
                                       0), axis=1)           # [C]
                blo = jnp.min(jnp.where(sim, k0, NB + 1))
                bhi = jnp.max(jnp.where(sim, nblk, 0))
                carry = snap_read(snaps, k0)

                def block(bs):
                    b, carry, snaps = bs
                    act = sim & (k0 <= b) & (b < nblk)
                    off = b * _BLOCK
                    xs = tuple(
                        lax.dynamic_slice(cc, (jnp.zeros_like(off), off),
                                          (C, _BLOCK))
                        for cc in lane_cols)
                    idx = (off + jnp.arange(_BLOCK)).astype(f64)
                    new = lane_sim(carry, xs, idx, design, bucket)[0]
                    carry = tree(
                        lambda a, n: jnp.where(
                            act[:, None] if n.ndim == 2 else act, n, a),
                        carry, new)
                    snaps = snap_write(snaps, b, act, carry)
                    return b + 1, carry, snaps

                bF, carry, snaps = lax.while_loop(
                    lambda bs: bs[0] < bhi, block, (blo, carry, snaps))
                return carry[7], carry[12], snaps, bF - blo

            def round_body(st):
                (wsum, nw, ends, lg, te, r, _, mxn, snaps, blk,
                 p_sh, p_nsh, p_tail) = st
                closed = live & jnp.isfinite(ends)
                horizon = jnp.maximum(
                    d, jnp.max(jnp.where(closed, ends, d)))
                k = jnp.arange(S, dtype=f64)
                e = d + k                                       # [S]
                hi = jnp.where(jnp.isinf(ends), horizon, ends)  # [C]
                act = (live[:, None] & (start[:, None] <= e[None, :])
                       & (e[None, :] < hi[:, None]))
                open_ = live & jnp.isinf(ends)
                # per-epoch weight sums, folded in the host's span order
                # (masked adds of +0.0 are exact, so dead lanes are
                # order-transparent; unit weights reduce to the integer
                # count and stay exact in any order)
                win = jnp.zeros((S,), f64)
                wf = jnp.asarray(0.0, f64)
                for j in range(C):
                    lane = perm[j]
                    win = win + jnp.where(act[lane], w_lane[lane], 0.0)
                    wf = wf + jnp.where(open_[lane], w_lane[lane], 0.0)
                wsum = lax.dynamic_update_slice(
                    wsum, win, ((d - base2).astype(i32),))
                n_sh = jnp.where(jnp.isinf(ends), horizon - start,
                                 ends - start)
                mxn = jnp.maximum(mxn,
                                  jnp.max(jnp.where(need, n_sh, 0.0)))
                n_sh = jnp.clip(n_sh, 0.0, float(S))
                tail = jnp.where(open_, budget * w_lane / wf, budget)
                lidx = jnp.clip(
                    (start[:, None] - base2).astype(i32)
                    + jnp.arange(S, dtype=i32)[None, :], 0, W - 1)
                shares = budget * w_lane[:, None] / wsum[lidx]  # [C, S]
                bucket = (shares, n_sh, E, tail, burst, n_sh * E,
                          charge_store, store_free, inv_store, inv_load)
                # first epoch whose visible share differs from the lane's
                # previous sim: epochs below it replay identically, so an
                # unchanged lane is skipped outright (the host relaxation's
                # unchanged-visibility skip) and a changed one resumes from
                # its deepest snapshot before the divergence
                m = jnp.minimum(n_sh, p_nsh)
                diff = (k[None, :] < m[:, None]) & (shares != p_sh)
                fc = jnp.min(jnp.where(diff, k[None, :], INF), axis=1)
                cap = jnp.where((n_sh != p_nsh) | (tail != p_tail), m, INF)
                fc = jnp.minimum(fc, cap)
                sim = need & jnp.isfinite(fc)
                te_n, lg_n, snaps, nblks = resim(snaps, bucket, sim, fc)
                te = jnp.where(sim, te_n, te)
                lg = jnp.where(sim, lg_n, lg)
                sel = sim[:, None]
                p_sh = jnp.where(sel, shares, p_sh)
                p_nsh = jnp.where(sim, n_sh, p_nsh)
                p_tail = jnp.where(sim, tail, p_tail)
                e_new = start + jnp.floor(lg / E) + 1.0
                e_new = jnp.where(need, jnp.minimum(e_new, ends), ends)
                conv = jnp.all(e_new == ends)
                return (wsum, horizon, e_new, lg, te, r + 1, conv, mxn,
                        snaps, blk + nblks, p_sh, p_nsh, p_tail)

            st = (wsum, nw, ends, lg, te, jnp.int32(0),
                  jnp.asarray(False), mxn, snaps, jnp.int32(0),
                  p_sh, p_nsh, p_tail)
            st = lax.while_loop(
                lambda s: (~s[6]) & (s[5] < max_rounds), round_body, st)
            return (st[0], base2, st[1], st[2], st[3], st[4], st[7],
                    st[8], st[5], st[9], st[10], st[11], st[12])

        def outer_body(c):
            (qhead, qtail, qidx, qsub, tid, cur, start, ends, lg, te,
             wsum, base, nw, finish, adm_ep, mxn, snaps, n_r, n_b,
             p_sh, p_nsh, p_tail, n_arr, adm, dec_done, t_dec) = c
            has_q = qhead < qtail
            alive = jnp.any(has_q)
            if reactive:
                alive = alive | (adm < n_real)
            slot = jnp.minimum(qhead, maxq - 1)
            nxt_s = jnp.clip(qidx[lanes, slot], 0, N - 1)
            sub = qsub[lanes, slot]
            free = jnp.maximum(start, jnp.ceil((start * E + te) / E))
            free = jnp.where(tid >= 0, free, 0.0)
            b_c = jnp.where(has_q, jnp.maximum(free, sub), INF)
            bstar = jnp.min(b_c)

            def start_step(c):
                """Pump: all cores sharing the minimal boundary start
                their queue heads together, then the arbiter settles."""
                (qhead, qtail, qidx, qsub, tid, cur, start, ends, lg, te,
                 wsum, base, nw, finish, adm_ep, mxn, snaps, n_r, n_b,
                 p_sh, p_nsh, p_tail, n_arr, adm, dec_done, t_dec) = c
                starts = has_q & (b_c == bstar)
                tid2 = jnp.where(starts, tid_of[nxt_s], tid)
                cur2 = jnp.where(starts, nxt_s, cur)
                start2 = jnp.where(starts, bstar, start)
                ends2 = jnp.where(starts, INF, ends)
                lg2 = jnp.where(starts, 0.0, lg)
                te2 = jnp.where(starts, 0.0, te)
                qhead2 = qhead + starts.astype(qhead.dtype)
                snaps2 = reset_snaps(snaps, starts)
                # a fresh span has no previous sim: p_nsh = -1 forces a
                # full first simulation and invalidates old snapshots
                p_nsh2 = jnp.where(starts, -1.0, p_nsh)
                p_tail2 = jnp.where(starts, -1.0, p_tail)
                # the boundary event reopens every span still active here
                ends2 = jnp.where((tid2 >= 0) & (ends2 > bstar), INF,
                                  ends2)
                (wsum2, base2, nw2, ends2, lg2, te2, mxn2, snaps2, dn_r,
                 dn_b, p_sh2, p_nsh2, p_tail2) = settle(
                    wsum, base, nw, tid2, cur2, start2, ends2, lg2, te2,
                    snaps2, bstar, mxn, p_sh, p_nsh2, p_tail2)
                fslot = jnp.where(tid2 >= 0, cur2, N)
                finish2 = finish.at[fslot].set(
                    jnp.where(tid2 >= 0, start2 * E + te2, finish[fslot]))
                return (qhead2, qtail, qidx, qsub, tid2, cur2, start2,
                        ends2, lg2, te2, wsum2, base2, nw2, finish2,
                        adm_ep, mxn2, snaps2, n_r + dn_r, n_b + dn_b,
                        p_sh2, p_nsh2, p_tail2, n_arr, adm, dec_done,
                        t_dec)

            if not reactive:
                new = start_step(c)
                return tree(lambda a, b: jnp.where(alive, a, b), new, c)

            def admit_step(c):
                """The host driver's decision epoch at ``t_dec``: enqueue
                arrivals, admit under the policy, record admit epochs."""
                (qhead, qtail, qidx, qsub, tid, cur, start, ends, lg, te,
                 wsum, base, nw, finish, adm_ep, mxn, snaps, n_r, n_b,
                 p_sh, p_nsh, p_tail, n_arr, adm, dec_done, t_dec) = c
                t = t_dec
                n_arr2 = jnp.searchsorted(arrival, t,
                                          side="right").astype(i32)
                n_wait = n_arr2 - adm
                n_act = jnp.sum(((tid >= 0) & (start <= t)
                                 & (ends > t)).astype(i32))
                kj = jnp.arange(kmax)
                # the host's headroom walk: count k while the projected
                # per-request share stays at or above the floor
                h = jnp.sum(((kj < n_wait)
                             & (budget / (n_act + kj + 1).astype(f64)
                                >= min_share)).astype(i32))
                cap = jnp.minimum(n_wait, h)
                busy = (free > t) | has_q

                def free_at():
                    # the host's free_at_estimate: settled finish of
                    # started work, clamped to now, plus unthrottled cost
                    # estimates folded in queue order
                    fa = jnp.maximum(
                        jnp.where(tid >= 0, start * E + te, 0.0), t * E)
                    depth = qtail - qhead

                    def fold(j, fa):
                        sl = jnp.minimum(qhead + j, maxq - 1)
                        u = tid_of[jnp.clip(qidx[lanes, sl], 0, N - 1)]
                        return fa + jnp.where(j < depth, est[u, lanes],
                                              0.0)

                    return lax.fori_loop(0, jnp.max(depth), fold, fa)

                fa = free_at()
                qidx2, qsub2, qtail2 = qidx, qsub, qtail
                if mode == "occupancy":
                    nfree = jnp.sum((~busy).astype(i32))
                    take = jnp.minimum(cap, nfree)
                    pick = ~busy
                    # rank among the picked cores, ascending core index
                    rank = (jnp.cumsum(pick.astype(i32))
                            - pick.astype(i32)).astype(i32)
                elif mode == "predicted":
                    hz = (t + lookahead) * E
                    elig = fa <= hz
                    take = jnp.minimum(cap, jnp.sum(elig.astype(i32)))
                    pick = elig
                    # the host's stable sort by free_at: rank = count of
                    # eligible cores strictly (fa, index)-before this one
                    before = (elig[None, :]
                              & ((fa[None, :] < fa[:, None])
                                 | ((fa[None, :] == fa[:, None])
                                    & (lanes[None, :] < lanes[:, None]))))
                    rank = jnp.sum(before.astype(i32), axis=1).astype(i32)
                if mode in ("occupancy", "predicted"):
                    sel = pick & (rank < take)
                    col = jnp.minimum(qtail, maxq - 1)
                    qidx2 = qidx.at[lanes, col].set(
                        jnp.where(sel, adm + rank, qidx[lanes, col]))
                    qsub2 = qsub.at[lanes, col].set(
                        jnp.where(sel, t, qsub[lanes, col]))
                    qtail2 = qtail + sel.astype(qtail.dtype)
                else:   # bandwidth: headroom-gated, soonest-free placed
                    take = cap
                    fe = fa
                    for j in range(kmax):
                        on = jnp.asarray(j, i32) < take
                        rank_j = adm + j
                        u_j = tid_of[jnp.clip(rank_j, 0, N - 1)]
                        key = fe + est[u_j]
                        cj = jnp.argmin(key)    # first-minimal, as host
                        fe = jnp.where((lanes == cj) & on, key, fe)
                        colj = jnp.minimum(qtail2[cj], maxq - 1)
                        qidx2 = qidx2.at[cj, colj].set(
                            jnp.where(on, rank_j, qidx2[cj, colj]))
                        qsub2 = qsub2.at[cj, colj].set(
                            jnp.where(on, t, qsub2[cj, colj]))
                        qtail2 = qtail2 + jnp.where((lanes == cj) & on,
                                                    1, 0).astype(
                                                        qtail2.dtype)
                wsl = jnp.where(kj < take, adm + kj, N)
                adm_ep2 = adm_ep.at[wsl].set(t)
                # work conservation: a threshold policy must not starve a
                # waiting request on an idle chip -- the host admits one
                # onto the soonest-free core past the headroom floor
                wc = (take == 0) & (n_wait > 0) & jnp.all(~busy)
                u_wc = tid_of[jnp.clip(adm, 0, N - 1)]
                cw = jnp.argmin(fa + est[u_wc])
                colw = jnp.minimum(qtail2[cw], maxq - 1)
                qidx2 = qidx2.at[cw, colw].set(
                    jnp.where(wc, adm, qidx2[cw, colw]))
                qsub2 = qsub2.at[cw, colw].set(
                    jnp.where(wc, t, qsub2[cw, colw]))
                qtail2 = qtail2 + jnp.where((lanes == cw) & wc,
                                            1, 0).astype(qtail2.dtype)
                adm_ep2 = adm_ep2.at[jnp.where(wc, adm, N)].set(
                    jnp.where(wc, t, adm_ep2[jnp.where(wc, adm, N)]))
                adm2 = (adm + take + wc.astype(i32)).astype(i32)
                # t_dec == dec_done marks "recompute after the pump":
                # the next decision epoch is derived from post-start
                # state, exactly where the host derives it
                return (qhead, qtail2, qidx2, qsub2, tid, cur, start,
                        ends, lg, te, wsum, base, nw, finish, adm_ep2,
                        mxn, snaps, n_r, n_b, p_sh, p_nsh, p_tail,
                        n_arr2, adm2, t, t)

            def resched_step(c):
                """Recompute the next decision epoch from the settled
                post-pump state: the host's candidate list -- the next
                arrival always, the chip's next event only while
                requests wait."""
                (qhead, qtail, qidx, qsub, tid, cur, start, ends, lg, te,
                 wsum, base, nw, finish, adm_ep, mxn, snaps, n_r, n_b,
                 p_sh, p_nsh, p_tail, n_arr, adm, dec_done, t_dec) = c
                cand_arr = jnp.where(
                    n_arr < n_real,
                    arrival[jnp.clip(n_arr, 0, N - 1)], INF)
                f_evt = jnp.where(has_q, jnp.maximum(free, sub), free)
                isev = ((tid >= 0) | has_q) & (f_evt > dec_done)
                evt = jnp.min(jnp.where(isev, f_evt, INF))
                t2 = jnp.minimum(cand_arr,
                                 jnp.where(n_arr > adm, evt, INF))
                # unreachable backstop (an idle chip with waiting work
                # always admits): never spin on an inf decision epoch
                adm2 = jnp.where(jnp.isinf(t2) & (n_arr >= n_real),
                                 n_real, adm).astype(i32)
                return (qhead, qtail, qidx, qsub, tid, cur, start, ends,
                        lg, te, wsum, base, nw, finish, adm_ep, mxn,
                        snaps, n_r, n_b, p_sh, p_nsh, p_tail, n_arr,
                        adm2, dec_done, t2)

            dec_done, t_dec = c[24], c[25]
            new = lax.cond(
                bstar <= t_dec, start_step,
                lambda c: lax.cond(t_dec > dec_done, admit_step,
                                   resched_step, c), c)
            return tree(lambda a, b: jnp.where(alive, a, b), new, c)

        z = jnp.zeros((C,), f64)
        c0 = (jnp.zeros(C, jnp.int32), qtail0.astype(jnp.int32),
              qidx0.astype(jnp.int32), qsub0.astype(f64),
              jnp.full((C,), -1, jnp.int32), jnp.zeros(C, jnp.int32),
              z, jnp.full((C,), -INF, f64), z, z,
              jnp.zeros((W,), f64), jnp.asarray(0.0, f64),
              jnp.asarray(0.0, f64),
              jnp.zeros((N + 1,), f64), jnp.zeros((N + 1,), f64),
              jnp.asarray(0.0, f64), blank_snaps(),
              jnp.int32(0), jnp.int32(0),
              jnp.zeros((C, S), f64), jnp.full((C,), -1.0, f64),
              jnp.full((C,), -1.0, f64),
              jnp.int32(0), jnp.int32(0) if reactive else n_real,
              jnp.asarray(-INF, f64),
              arrival[0] if reactive else jnp.asarray(INF, f64))

        def cond(c):
            alive = jnp.any(c[0] < c[1])
            if reactive:
                alive = alive | (c[23] < n_real)
            return alive

        cF = lax.while_loop(cond, outer_body, c0)
        return cF[13][:N], cF[14][:N], cF[15], cF[17], cF[18]

    one = jax.jit(functools.partial(program, packed=True))
    many = jax.jit(jax.vmap(
        functools.partial(program, packed=False),
        in_axes=((None, None, None, None, None, 0, 0, 0, None, 0)
                 + (None,) * 9)))
    return one, many


def _launch_args(p: Plan):
    params = [stream_model_params(p.chip, cs.engine)
              for cs in p.chip.core_specs]
    store_free = params[0].store_ports is None
    statics = (p.chip.n_cores, len(p.arrival), p.maxq,
               p.cols[0].shape[0], p.t2l.shape[0], p.cols[0].shape[1],
               p.S, p.mode, bool(params[0].charge_store_bytes),
               store_free, p.kmax, MAX_ARBITER_ROUNDS)
    design = _design_arrays([cs.engine for cs in p.chip.core_specs])
    arrays = (np.float64(p.chip.epoch_cycles),
              np.float64(p.chip.bw_bytes_per_cycle),
              np.float64(p.chip.bw_burst_bytes),
              np.asarray([1.0 / pp.load_ports for pp in params]),
              np.asarray([1.0 if pp.store_ports is None
                          else 1.0 / pp.store_ports for pp in params]),
              design, np.float64(p.min_share), np.float64(p.lookahead),
              np.int32(p.n_real))
    return statics, arrays


def _check(p: Plan, mxn: float) -> None:
    if mxn > p.S:
        raise RuntimeError(
            f"jitted arbitration window bound violated (span epochs "
            f"{mxn} vs window {p.S}): the host span bound is unsound "
            f"here")


def finish_admit_times(p: Plan, stats: dict | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Run one planned trace; (finish cycles, admit epochs) in caller
    order.

    When ``stats`` is given, the kernel's relaxation-round and
    simulated-block counters are recorded into it (benchmark
    diagnostics).
    """
    statics, arrays = _launch_args(p)
    fn = _kernel(*statics)[0]
    with x64():
        fin, adm, mxn, n_r, n_b = fn(p.cols, p.tr_len, p.t2l, p.wt,
                                     p.est, p.arrival, p.qidx, p.qsub,
                                     p.qtail0, p.tid_of, *arrays)
        fin = np.asarray(fin)
        adm = np.asarray(adm)
        _check(p, float(mxn))
        if stats is not None:
            stats["rounds"] = int(n_r)
            stats["blocks"] = int(n_b)
    out = np.zeros(p.n_real, dtype=np.float64)
    out[p.order] = fin[:p.n_real]
    adm_out = np.zeros(p.n_real, dtype=np.float64)
    adm_out[p.order] = p.adm_fixed if p.mode == "fixed" \
        else adm[:p.n_real]
    return out, adm_out


def finish_times(p: Plan, stats: dict | None = None) -> np.ndarray:
    """Run one planned trace; absolute finish cycles in caller order."""
    return finish_admit_times(p, stats)[0]


def finish_times_many(plans: Sequence[Plan]) -> list[np.ndarray]:
    """Run a family of same-shape plans (e.g. an arrival-rate sweep) as
    one vmapped launch.  All plans must come from :func:`plan_many`."""
    head = plans[0]
    statics, arrays = _launch_args(head)
    fn = _kernel(*statics)[1]
    with x64():
        fin, _, mxn, _, _ = fn(head.cols, head.tr_len, head.t2l, head.wt,
                               head.est,
                               np.stack([p.arrival for p in plans]),
                               np.stack([p.qidx for p in plans]),
                               np.stack([p.qsub for p in plans]),
                               head.qtail0,
                               np.stack([p.tid_of for p in plans]),
                               *arrays)
        fin = np.asarray(fin)
        for p, x in zip(plans, np.asarray(mxn)):
            _check(p, float(x))
    outs = []
    for v, p in enumerate(plans):
        out = np.zeros(p.n_real, dtype=np.float64)
        out[p.order] = fin[v][:p.n_real]
        outs.append(out)
    return outs


def plan_many(traffics: Sequence[Sequence[tuple[int, Sequence[GemmSpec]]]],
              chip: ChipConfig) -> list[Plan] | None:
    """Plan several ``fixed``-admission arrival traces over the *same*
    request-shape universe so they share one executable (common trace
    table, window and queue bounds).  Returns ``None`` if any variant
    falls outside the domain or the variants disagree on request count."""
    plans = [plan(t, chip) for t in traffics]
    if any(p is None for p in plans) or not plans:
        return None
    if {len(p.arrival) for p in plans} != {len(plans[0].arrival)} \
            or {p.n_real for p in plans} != {plans[0].n_real} \
            or {p.qtail0.tobytes() for p in plans} \
            != {plans[0].qtail0.tobytes()}:
        return None
    C = chip.n_cores
    # unify trace rows by content, then request shapes by their per-core
    # row vector, so every variant indexes one shared table
    row_of: dict[bytes, int] = {}
    all_rows: list[tuple] = []
    all_len: list[int] = []
    L = max(p.cols[0].shape[1] for p in plans)
    shape_of: dict[tuple, int] = {}
    shape_rows: list[tuple] = []
    shape_wt: list[np.ndarray] = []
    shape_est: list[np.ndarray] = []
    remap_u: list[np.ndarray] = []
    for p in plans:
        row_ids = np.zeros(p.cols[0].shape[0], dtype=np.int32)
        for r in range(p.cols[0].shape[0]):
            pad = L - p.cols[0].shape[1]
            row = tuple(
                np.concatenate([c[r], np.full(pad, OP_NOP if f == 0
                                              else 0, dtype=c[r].dtype)])
                for f, c in enumerate(p.cols))
            sig = b"".join(np.ascontiguousarray(a).tobytes()
                           for a in row)
            t = row_of.get(sig)
            if t is None:
                t = row_of[sig] = len(all_rows)
                all_rows.append(row)
                all_len.append(int(p.tr_len[r]))
            row_ids[r] = t
        uids = np.zeros(p.t2l.shape[0], dtype=np.int32)
        for u in range(p.t2l.shape[0]):
            key = tuple(int(row_ids[p.t2l[u, c]]) for c in range(C))
            g = shape_of.get(key)
            if g is None:
                g = shape_of[key] = len(shape_rows)
                shape_rows.append(key)
                shape_wt.append(p.wt[u])
                shape_est.append(p.est[u])
            uids[u] = g
        remap_u.append(uids)
    cols = tuple(np.stack([rw[f] for rw in all_rows]) for f in range(7))
    cols, tr_len = _nop_rows(cols,
                             np.asarray(all_len, dtype=np.int32),
                             _pow2(len(all_rows), lo=1))
    U = _pow2(len(shape_rows), lo=1)
    t2l = np.zeros((U, C), dtype=np.int32)
    wt = np.ones((U, C), dtype=np.float64)
    est = np.zeros((U, C), dtype=np.float64)
    for g, key in enumerate(shape_rows):
        t2l[g] = key
        wt[g] = shape_wt[g]
        est[g] = shape_est[g]
    S = max(p.S for p in plans)
    maxq = max(p.maxq for p in plans)
    out = []
    for p, uids in zip(plans, remap_u):
        qidx = np.zeros((C, maxq), dtype=np.int32)
        qidx[:, :p.qidx.shape[1]] = p.qidx
        qsub = np.zeros((C, maxq), dtype=np.float64)
        qsub[:, :p.qsub.shape[1]] = p.qsub
        out.append(dataclasses.replace(
            p, cols=cols, tr_len=tr_len, t2l=uids[p.t2l], wt=wt, est=est,
            tid_of=uids[p.tid_of], qidx=qidx, qsub=qsub, S=S, maxq=maxq))
    return out
