"""Fast simulation backends over :class:`repro.core.trace.CompiledTrace`.

The reference :class:`repro.core.timing.PipelineSimulator` is the exactness
oracle: a pure-Python per-instruction loop over ``Instr`` objects.  Its
scheduling recurrence, however, is a small fixed-size carry -- eight
register ready-times, the previous instruction's four sub-stage times, the
WL-port/LSQ-port free times and the token-bucket state -- which makes it
exactly a ``jax.lax.scan`` step.  This module implements that step twice:

``numpy``
    A Python loop over the compiled SoA trace that calls the *same*
    ``LoadStreamModel`` objects as the reference simulator.  Bit-exact with
    the reference by construction (identical arithmetic in identical
    order); 3-6x faster because the per-instruction ``Instr``/
    ``TileRegisterFile`` bookkeeping is precompiled away.  This is the
    fallback when jax is unavailable or the stream is too short to amortize
    a compile.

``jax``
    ``jax.lax.scan`` over the trace arrays, ``vmap``-batched over designs
    (one trace, eight engine configs -- the ``sweep_designs`` fast path) or
    over cores (one config, N per-core traces under a shared epoch-share
    schedule -- the ``multicore`` arbiter fast path).  Runs in float64 via
    the scoped :func:`x64` context so the global jax configuration is
    untouched; agrees with the reference to well below
    the 1e-6 relative parity bound (see ``tests/test_fastsim.py``).

The load/store arbitration of *both* the paper's idealized port model and
the chip-level token buckets is expressed by one parameter set,
:class:`StreamModelParams`: an empty share schedule with an infinite tail
share reduces exactly to the unthrottled port model (the same reduction
``SharedBandwidthLoadModel`` documents).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import numpy as np

from . import probe
from .designs import EngineConfig
from .isa import NUM_TREGS
from .timing import LoadStreamModel, TimingResult
from .trace import OP_END, OP_MM, OP_TL, OP_TS, CompiledTrace

#: below this many total instructions (batch x length) the scan's compile +
#: dispatch overhead beats the win, and ``backend="fast"`` stays on numpy.
FAST_JAX_MIN_INSTRS = 32768

#: per-core batches (each lane its own trace: gather-bound scan step) need
#: far more work before the jax path beats the inlined numpy loop.
FAST_JAX_MIN_CORES_INSTRS = 4_000_000

_BACKENDS = ("fast", "numpy", "jax")


def x64():
    """Scoped float64 for the jax programs (``with x64(): ...``).

    Every jitted simulator program traces, runs and reads back its results
    inside this context, so it matches the numpy oracle bit for bit while
    the global jax configuration stays untouched.
    """
    import jax
    return jax.enable_x64(True)


def resolve_backend(backend: str, n_instrs: int) -> str:
    """Map a requested backend to a concrete one (``numpy`` or ``jax``).

    ``fast`` auto-selects: jax when the batch is large enough (>=
    ``FAST_JAX_MIN_INSTRS`` instructions) to amortize compilation; numpy
    otherwise.
    """
    if backend in ("numpy", "jax"):
        return backend
    if backend == "fast":
        return "jax" if n_instrs >= FAST_JAX_MIN_INSTRS else "numpy"
    raise ValueError(f"unknown backend {backend!r}; available: {_BACKENDS} "
                     f"(plus 'reference' at the simulator facade)")


# --------------------------------------------------------------------------
# load/store stream-model parameters
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamModelParams:
    """Declarative form of a :class:`LoadStreamModel` for the fast backends.

    The defaults describe the paper's idealized LSQ (``load_ports`` loads
    per cycle, free stores, no bandwidth cap): an empty epoch schedule whose
    infinite ``tail_share`` never throttles.  Chip-level arbiters fill in
    the token-bucket fields (cf. ``repro.multicore.chip``).
    """

    load_ports: int
    store_ports: int | None = None
    shares: tuple[float, ...] = ()
    epoch_cycles: float = math.inf
    tail_share: float = math.inf
    burst_bytes: float = 0.0
    charge_store_bytes: bool = False

    def __post_init__(self):
        if not self.epoch_cycles > 0:
            raise ValueError("epoch_cycles must be > 0")
        if not self.tail_share > 0:
            raise ValueError("tail_share must be > 0 (requests past the "
                             "schedule could never be granted)")

    @property
    def is_port_model(self) -> bool:
        return not self.shares and math.isinf(self.tail_share)

    @classmethod
    def for_config(cls, cfg: EngineConfig) -> "StreamModelParams":
        return cls(load_ports=cfg.load_ports)

    @classmethod
    def from_model(cls, model: LoadStreamModel) -> "StreamModelParams | None":
        """Extract parameters from a live model, or None when the model is a
        custom subclass whose semantics the fast backends cannot replicate
        (callers then fall back to the reference simulator)."""
        if type(model) is LoadStreamModel:
            return cls(model.load_ports, model.store_ports)
        try:
            from ..multicore.chip import EpochBandwidthLoadModel
        except ImportError:                              # pragma: no cover
            return None
        cls_ = type(model)
        untouched = all(
            getattr(cls_, m) is getattr(EpochBandwidthLoadModel, m)
            for m in ("acquire", "acquire_store", "reset", "_grant",
                      "_advance", "_share_at"))
        if (isinstance(model, EpochBandwidthLoadModel) and untouched
                and not model.record_grants):
            return cls(model.load_ports, model.store_ports,
                       tuple(model.shares), model.epoch_cycles,
                       model.tail_share, model.burst_bytes,
                       model.charge_store_bytes)
        return None

    def make_model(self) -> LoadStreamModel:
        """Instantiate the live model these parameters describe (the numpy
        backend runs the recurrence against real model objects so it stays
        bit-exact with the reference simulator)."""
        if self.is_port_model:
            return LoadStreamModel(self.load_ports, self.store_ports)
        from ..multicore.chip import EpochBandwidthLoadModel
        return EpochBandwidthLoadModel(
            self.load_ports, self.shares, self.epoch_cycles, self.tail_share,
            burst_bytes=self.burst_bytes, store_ports=self.store_ports,
            charge_store_bytes=self.charge_store_bytes)

    @property
    def schedule_end(self) -> float:
        return len(self.shares) * self.epoch_cycles if self.shares else 0.0


def _result(trace: CompiledTrace, cfg: EngineConfig, t_end: float,
            wl_skips: int, bw_stall: float) -> TimingResult:
    return TimingResult(
        cycles=float(t_end), n_mm=trace.n_mm, n_tl=trace.n_tl,
        n_ts=trace.n_ts, wl_skips=int(wl_skips),
        useful_macs=trace.useful_macs,
        peak_macs_per_cycle=cfg.peak_macs_per_cycle,
        bw_stall_cycles=float(bw_stall), schedules=None)


# --------------------------------------------------------------------------
# numpy backend: SoA loop against live LoadStreamModel objects
# --------------------------------------------------------------------------

def run_trace_numpy(trace: CompiledTrace, cfg: EngineConfig,
                    load_model: LoadStreamModel | None = None) -> TimingResult:
    """Run the scheduling recurrence over a compiled trace.

    Mirrors ``PipelineSimulator.run`` statement for statement (same
    arithmetic, same order, same model calls) -- the dirty-bit bookkeeping
    is the only thing replaced, by the trace's precompiled ``reusable``
    bits.  Bit-exact with the reference.
    """
    wl = cfg.wl_cycles
    fs = cfg.fs_cycles
    dr = cfg.dr_cycles
    issue_per_cycle = cfg.core_issue_width * (cfg.core_clock_hz
                                              / cfg.engine_clock_hz)
    load_lat = float(cfg.load_latency)
    model = load_model or LoadStreamModel(cfg.load_ports)
    model.reset()
    acquire = model.acquire
    acquire_store = model.acquire_store
    wlbp, wls, pipe = cfg.wlbp, cfg.wls, cfg.pipe

    op = trace.opcode.tolist()
    rd = trace.r_dst.tolist()
    ra = trace.r_a.tolist()
    rb = trace.r_b.tolist()
    nb = trace.nbytes.tolist()
    tms = trace.tm.tolist()
    reus = trace.reusable.tolist()

    reg_ready = [0.0] * NUM_TREGS
    p_ff_start = -1.0
    p_ff_end = p_fs_end = p_dr_end = 0.0
    have_prev = False
    wl_port_free = 0.0
    t_end = 0.0
    wl_skips = 0
    bw_stall = 0.0

    for i in range(len(op)):
        o = op[i]
        t_issue = i / issue_per_cycle

        if o == OP_TL:
            start, stall = acquire(t_issue, nb[i])
            bw_stall += stall
            done = start + load_lat
            reg_ready[rd[i]] = done
            if done > t_end:
                t_end = done
            continue

        if o == OP_TS:
            r = reg_ready[ra[i]]
            t_avail = t_issue if t_issue > r else r
            start, stall = acquire_store(t_avail, nb[i])
            bw_stall += stall
            e = start + 1.0
            if e > t_end:
                t_end = e
            continue

        if o != OP_MM:          # OP_NOP padding
            continue

        c, a, b = rd[i], ra[i], rb[i]
        t_ready_ac = max(t_issue, reg_ready[a], reg_ready[c])
        t_ready_b = max(t_issue, reg_ready[b])
        reuse = wlbp and reus[i]

        if reuse:
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0)
            wl_skips += 1
        elif wls:
            wl_start = max(t_ready_b, p_ff_start if have_prev else 0.0,
                           wl_port_free)
            hidden = have_prev and wl_start <= p_fs_end
            weights_ready = (wl_start + 1.0) if hidden else (wl_start + wl)
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0,
                           weights_ready)
            wl_port_free = wl_start + wl
        elif pipe:
            wl_start = max(t_ready_b, p_fs_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl,
                           p_dr_end if have_prev else 0.0)
            wl_port_free = wl_start + wl
        else:  # BASE
            wl_start = max(t_ready_b, p_dr_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl)
            wl_port_free = wl_start + wl

        ff_end = ff_start + tms[i]
        fs_end = ff_end + fs
        dr_end = fs_end + dr
        reg_ready[c] = dr_end
        if dr_end > t_end:
            t_end = dr_end
        p_ff_start, p_ff_end, p_fs_end, p_dr_end = (ff_start, ff_end,
                                                    fs_end, dr_end)
        have_prev = True

    res = _result(trace, cfg, t_end, wl_skips, bw_stall)
    return res


@dataclasses.dataclass(frozen=True)
class SimCarry:
    """Resumable snapshot of the inlined numpy recurrence.

    Captures the complete per-core simulator state after the first ``i``
    instructions of a trace: register ready-times, the previous ``rasa_mm``
    sub-stage times, port/bucket state and the running aggregates.  The
    future of the recurrence depends on the past *only* through this state,
    so re-simulation may resume here instead of replaying the prefix --
    provided the arbiter's share schedule is unchanged on
    ``[0, self.horizon)``: every epoch the first ``i`` instructions could
    observe lies strictly below the horizon (grant walks never look past
    the epoch containing the granted start).

    The online chip model (:mod:`repro.multicore.online`) snapshots every
    ``SNAP_STRIDE`` instructions and, when an arrival changes the schedule
    from epoch ``x`` on, resumes each in-flight core from its latest
    snapshot with ``horizon <= x * epoch_cycles``.
    """

    i: int                          # instructions consumed (resume index)
    reg_ready: tuple[float, ...]
    p_ff_start: float
    p_ff_end: float
    p_fs_end: float
    p_dr_end: float
    have_prev: bool
    wl_port_free: float
    t_end: float
    wl_skips: int
    bw_stall: float
    next_free: float
    store_next: float
    last_grant: float
    tokens: float
    bt: float

    @property
    def horizon(self) -> float:
        """Latest point in time this state depends on (see class docs)."""
        return max(self.t_end, self.bt, self.next_free, self.store_next,
                   self.wl_port_free, self.last_grant, self.p_dr_end,
                   max(self.reg_ready))


#: snapshot cadence of :func:`run_segment` (instructions between carries);
#: power of two so the per-instruction check stays a single compare.
SNAP_STRIDE = 4096


def _run_numpy_params(trace: CompiledTrace, cfg: EngineConfig,
                      params: StreamModelParams
                      ) -> tuple[TimingResult, float]:
    """The numpy loop with the stream-model arithmetic inlined.

    Identical statement order and float operations as
    :func:`run_trace_numpy` driving a live ``LoadStreamModel`` /
    ``EpochBandwidthLoadModel`` (bit-exact; pinned by the parity suite),
    but without the per-access method-call chain -- the dominant cost of
    bandwidth-throttled runs.  Returns ``(result, last_grant)``.
    """
    res, lg, _ = run_segment(trace, cfg, params)
    return res, lg


def run_segment(trace: CompiledTrace, cfg: EngineConfig,
                params: StreamModelParams,
                carry: SimCarry | None = None,
                snap_stride: int | None = None
                ) -> tuple[TimingResult, float, list[SimCarry]]:
    """Resumable form of the inlined numpy loop.

    With ``carry`` given, simulation resumes at instruction ``carry.i``
    from the saved state instead of replaying the prefix -- exact whenever
    ``params``'s share schedule agrees with the schedule the carry was
    produced under on ``[0, carry.horizon)`` (see :class:`SimCarry`).
    With ``snap_stride`` set, a snapshot is recorded every that many
    instructions; the returned list is ordered by instruction index.
    Returns ``(result, last_grant, snapshots)``.
    """
    wl = cfg.wl_cycles
    fs = cfg.fs_cycles
    dr = cfg.dr_cycles
    issue_per_cycle = cfg.core_issue_width * (cfg.core_clock_hz
                                              / cfg.engine_clock_hz)
    load_lat = float(cfg.load_latency)
    wlbp, wls, pipe = cfg.wlbp, cfg.wls, cfg.pipe

    port = params.is_port_model
    inv_load = 1.0 / params.load_ports
    store_free = params.store_ports is None
    inv_store = 1.0 / params.store_ports if not store_free else 0.0
    charge = params.charge_store_bytes and not port
    shares = list(params.shares)
    n_sh = len(shares)
    E = params.epoch_cycles
    sched_end = params.schedule_end
    tail = params.tail_share
    burst = params.burst_bytes
    tokens = burst
    bt = 0.0

    def grant(tokens, bt, t_earliest, n_bytes):
        # == EpochBandwidthLoadModel._grant (with _advance inlined)
        while bt < t_earliest:
            rate = shares[int(bt // E)] if bt // E < n_sh else tail
            if bt >= sched_end:
                step_end = t_earliest
            else:
                e_end = (int(bt // E) + 1) * E
                step_end = t_earliest if t_earliest < e_end else e_end
            if math.isinf(rate):
                tokens = burst
            else:
                tokens = tokens + rate * (step_end - bt)
                if tokens > burst:
                    tokens = burst
            bt = step_end
        need = n_bytes if n_bytes < burst else burst
        if tokens >= need:
            start = t_earliest
        else:
            t, tk = bt, tokens
            while True:
                rate = shares[int(t // E)] if t // E < n_sh else tail
                if math.isinf(rate):
                    start = t
                    break
                if rate <= 0.0 and t >= sched_end:
                    raise RuntimeError("tail share must be > 0: request can "
                                       "never be granted")
                e_end = (int(t // E) + 1) * E
                if rate > 0.0:
                    t_hit = t + (need - tk) / rate
                    if t_hit <= e_end or t >= sched_end:
                        start = t_hit
                        break
                    tk += rate * (e_end - t)
                t = e_end
            if start < t_earliest:
                start = t_earliest
        while bt < start:
            rate = shares[int(bt // E)] if bt // E < n_sh else tail
            if bt >= sched_end:
                step_end = start
            else:
                e_end = (int(bt // E) + 1) * E
                step_end = start if start < e_end else e_end
            if math.isinf(rate):
                tokens = burst
            else:
                tokens = tokens + rate * (step_end - bt)
                if tokens > burst:
                    tokens = burst
            bt = step_end
        return start, tokens - n_bytes, bt

    op = trace.opcode.tolist()
    rd = trace.r_dst.tolist()
    ra = trace.r_a.tolist()
    rb = trace.r_b.tolist()
    nb = trace.nbytes.tolist()
    tms = trace.tm.tolist()
    reus = trace.reusable.tolist()

    if carry is None:
        i0 = 0
        reg_ready = [0.0] * NUM_TREGS
        p_ff_start = -1.0
        p_ff_end = p_fs_end = p_dr_end = 0.0
        have_prev = False
        wl_port_free = 0.0
        t_end = 0.0
        wl_skips = 0
        bw_stall = 0.0
        next_free = store_next = 0.0
        last_grant = 0.0
    else:
        i0 = carry.i
        reg_ready = list(carry.reg_ready)
        p_ff_start = carry.p_ff_start
        p_ff_end = carry.p_ff_end
        p_fs_end = carry.p_fs_end
        p_dr_end = carry.p_dr_end
        have_prev = carry.have_prev
        wl_port_free = carry.wl_port_free
        t_end = carry.t_end
        wl_skips = carry.wl_skips
        bw_stall = carry.bw_stall
        next_free = carry.next_free
        store_next = carry.store_next
        last_grant = carry.last_grant
        tokens = carry.tokens
        bt = carry.bt

    snaps: list[SimCarry] = []
    next_snap = len(op) + 1
    if snap_stride is not None:
        next_snap = (i0 // snap_stride + 1) * snap_stride
        if carry is not None:
            # the boundary snapshot: a resumed run re-emits its carry-in,
            # so the returned list is self-contained -- the state at i0
            # is recorded even when resuming exactly on a stride boundary
            # (callers that re-seed from returned snaps would otherwise
            # lose the i0 checkpoint and replay up to a full stride)
            snaps.append(carry)

    for i in range(i0, len(op)):
        if i == next_snap:
            snaps.append(SimCarry(
                i, tuple(reg_ready), p_ff_start, p_ff_end, p_fs_end,
                p_dr_end, have_prev, wl_port_free, t_end, wl_skips,
                bw_stall, next_free, store_next, last_grant, tokens, bt))
            next_snap += snap_stride
        o = op[i]
        t_issue = i / issue_per_cycle

        if o == OP_TL:
            port_start = t_issue if t_issue > next_free else next_free
            if port:
                start = port_start
            else:
                start, tokens, bt = grant(tokens, bt, port_start, nb[i])
                bw_stall += start - port_start
            next_free = start + inv_load
            if start > last_grant:
                last_grant = start
            done = start + load_lat
            reg_ready[rd[i]] = done
            if done > t_end:
                t_end = done
            continue

        if o == OP_TS:
            r = reg_ready[ra[i]]
            t_avail = t_issue if t_issue > r else r
            if store_free:
                e = t_avail + 1.0
            else:
                port_start = t_avail if t_avail > store_next else store_next
                if charge:
                    start, tokens, bt = grant(tokens, bt, port_start, nb[i])
                    bw_stall += start - port_start
                else:
                    start = port_start
                store_next = start + inv_store
                if start > last_grant:
                    last_grant = start
                e = start + 1.0
            if e > t_end:
                t_end = e
            continue

        if o != OP_MM:          # OP_NOP padding
            continue

        c, a, b = rd[i], ra[i], rb[i]
        t_ready_ac = max(t_issue, reg_ready[a], reg_ready[c])
        t_ready_b = max(t_issue, reg_ready[b])
        reuse = wlbp and reus[i]

        if reuse:
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0)
            wl_skips += 1
        elif wls:
            wl_start = max(t_ready_b, p_ff_start if have_prev else 0.0,
                           wl_port_free)
            hidden = have_prev and wl_start <= p_fs_end
            weights_ready = (wl_start + 1.0) if hidden else (wl_start + wl)
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0,
                           weights_ready)
            wl_port_free = wl_start + wl
        elif pipe:
            wl_start = max(t_ready_b, p_fs_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl,
                           p_dr_end if have_prev else 0.0)
            wl_port_free = wl_start + wl
        else:  # BASE
            wl_start = max(t_ready_b, p_dr_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl)
            wl_port_free = wl_start + wl

        ff_end = ff_start + tms[i]
        fs_end = ff_end + fs
        dr_end = fs_end + dr
        reg_ready[c] = dr_end
        if dr_end > t_end:
            t_end = dr_end
        p_ff_start, p_ff_end, p_fs_end, p_dr_end = (ff_start, ff_end,
                                                    fs_end, dr_end)
        have_prev = True

    return _result(trace, cfg, t_end, wl_skips, bw_stall), last_grant, snaps


def completed_prefix(trace: CompiledTrace, cfg: EngineConfig,
                     params: StreamModelParams, limit: float,
                     carry: SimCarry | None = None) -> int:
    """How many leading instructions of ``trace`` have fully retired by
    time ``limit`` (engine-local cycles) under ``params``'s schedule.

    This is the deterministic preemption replay
    (:mod:`repro.multicore.faults`): when a core goes down at an epoch
    boundary, the surviving prefix of its in-flight segment is exactly the
    instructions whose *completion* -- load data arrival for ``rasa_tl``,
    store retire for ``rasa_ts``, drain end for ``rasa_mm`` -- lands at or
    before the boundary.  The loop mirrors :func:`run_segment` statement
    for statement (same arithmetic, same order, so the cut index is
    bit-identical on every backend) and stops at the first instruction
    that completes after ``limit``: returns ``k`` such that instructions
    ``[0, k)`` are done and instruction ``k`` is not.

    ``carry`` resumes the replay from a :class:`SimCarry` recorded by
    :func:`run_segment` under the *same* ``params`` schedule.  Valid only
    when ``carry.t_end <= limit``: ``t_end`` is the max completion time
    over instructions ``[0, carry.i)``, so none of them can be the first
    violator and the cut from ``carry.i`` on is bit-identical to the
    full replay -- repeated preemptions of one segment then replay only
    the work past its latest checkpoint instead of its whole history.
    """
    if carry is not None and carry.t_end > limit:
        raise ValueError("completed_prefix carry is past the limit: an "
                         "instruction before carry.i may be the cut")
    wl = cfg.wl_cycles
    fs = cfg.fs_cycles
    dr = cfg.dr_cycles
    issue_per_cycle = cfg.core_issue_width * (cfg.core_clock_hz
                                              / cfg.engine_clock_hz)
    load_lat = float(cfg.load_latency)
    wlbp, wls, pipe = cfg.wlbp, cfg.wls, cfg.pipe

    port = params.is_port_model
    inv_load = 1.0 / params.load_ports
    store_free = params.store_ports is None
    inv_store = 1.0 / params.store_ports if not store_free else 0.0
    charge = params.charge_store_bytes and not port
    shares = list(params.shares)
    n_sh = len(shares)
    E = params.epoch_cycles
    sched_end = params.schedule_end
    tail = params.tail_share
    burst = params.burst_bytes
    tokens = burst
    bt = 0.0

    def grant(tokens, bt, t_earliest, n_bytes):
        # == run_segment's inlined EpochBandwidthLoadModel._grant
        while bt < t_earliest:
            rate = shares[int(bt // E)] if bt // E < n_sh else tail
            if bt >= sched_end:
                step_end = t_earliest
            else:
                e_end = (int(bt // E) + 1) * E
                step_end = t_earliest if t_earliest < e_end else e_end
            if math.isinf(rate):
                tokens = burst
            else:
                tokens = tokens + rate * (step_end - bt)
                if tokens > burst:
                    tokens = burst
            bt = step_end
        need = n_bytes if n_bytes < burst else burst
        if tokens >= need:
            start = t_earliest
        else:
            t, tk = bt, tokens
            while True:
                rate = shares[int(t // E)] if t // E < n_sh else tail
                if math.isinf(rate):
                    start = t
                    break
                if rate <= 0.0 and t >= sched_end:
                    raise RuntimeError("tail share must be > 0: request can "
                                       "never be granted")
                e_end = (int(t // E) + 1) * E
                if rate > 0.0:
                    t_hit = t + (need - tk) / rate
                    if t_hit <= e_end or t >= sched_end:
                        start = t_hit
                        break
                    tk += rate * (e_end - t)
                t = e_end
            if start < t_earliest:
                start = t_earliest
        while bt < start:
            rate = shares[int(bt // E)] if bt // E < n_sh else tail
            if bt >= sched_end:
                step_end = start
            else:
                e_end = (int(bt // E) + 1) * E
                step_end = start if start < e_end else e_end
            if math.isinf(rate):
                tokens = burst
            else:
                tokens = tokens + rate * (step_end - bt)
                if tokens > burst:
                    tokens = burst
            bt = step_end
        return start, tokens - n_bytes, bt

    op = trace.opcode.tolist()
    rd = trace.r_dst.tolist()
    ra = trace.r_a.tolist()
    rb = trace.r_b.tolist()
    nb = trace.nbytes.tolist()
    tms = trace.tm.tolist()
    reus = trace.reusable.tolist()

    if carry is None:
        i0 = 0
        reg_ready = [0.0] * NUM_TREGS
        p_ff_start = -1.0
        p_ff_end = p_fs_end = p_dr_end = 0.0
        have_prev = False
        wl_port_free = 0.0
        next_free = store_next = 0.0
    else:
        i0 = carry.i
        reg_ready = list(carry.reg_ready)
        p_ff_start = carry.p_ff_start
        p_ff_end = carry.p_ff_end
        p_fs_end = carry.p_fs_end
        p_dr_end = carry.p_dr_end
        have_prev = carry.have_prev
        wl_port_free = carry.wl_port_free
        next_free = carry.next_free
        store_next = carry.store_next
        tokens = carry.tokens
        bt = carry.bt

    for i in range(i0, len(op)):
        o = op[i]
        t_issue = i / issue_per_cycle

        if o == OP_TL:
            port_start = t_issue if t_issue > next_free else next_free
            if port:
                start = port_start
            else:
                start, tokens, bt = grant(tokens, bt, port_start, nb[i])
            next_free = start + inv_load
            done = start + load_lat
            if done > limit:
                return i
            reg_ready[rd[i]] = done
            continue

        if o == OP_TS:
            r = reg_ready[ra[i]]
            t_avail = t_issue if t_issue > r else r
            if store_free:
                e = t_avail + 1.0
            else:
                port_start = t_avail if t_avail > store_next else store_next
                if charge:
                    start, tokens, bt = grant(tokens, bt, port_start, nb[i])
                else:
                    start = port_start
                store_next = start + inv_store
                e = start + 1.0
            if e > limit:
                return i
            continue

        if o != OP_MM:          # OP_NOP padding: retires instantly
            continue

        c, a, b = rd[i], ra[i], rb[i]
        t_ready_ac = max(t_issue, reg_ready[a], reg_ready[c])
        t_ready_b = max(t_issue, reg_ready[b])
        reuse = wlbp and reus[i]

        if reuse:
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0)
        elif wls:
            wl_start = max(t_ready_b, p_ff_start if have_prev else 0.0,
                           wl_port_free)
            hidden = have_prev and wl_start <= p_fs_end
            weights_ready = (wl_start + 1.0) if hidden else (wl_start + wl)
            ff_start = max(t_ready_ac, p_ff_end if have_prev else 0.0,
                           weights_ready)
            wl_port_free = wl_start + wl
        elif pipe:
            wl_start = max(t_ready_b, p_fs_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl,
                           p_dr_end if have_prev else 0.0)
            wl_port_free = wl_start + wl
        else:  # BASE
            wl_start = max(t_ready_b, p_dr_end if have_prev else 0.0,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl)
            wl_port_free = wl_start + wl

        ff_end = ff_start + tms[i]
        fs_end = ff_end + fs
        dr_end = fs_end + dr
        if dr_end > limit:
            return i
        reg_ready[c] = dr_end
        p_ff_start, p_ff_end, p_fs_end, p_dr_end = (ff_start, ff_end,
                                                    fs_end, dr_end)
        have_prev = True

    return len(op)


# --------------------------------------------------------------------------
# jax backend: lax.scan step, vmapped over designs or cores
# --------------------------------------------------------------------------

def _pow2(n: int, lo: int = 16) -> int:
    return max(lo, 1 << max(0, (n - 1)).bit_length())


#: the jax backend scans fixed-size chunks and threads the carry between
#: them, so changing stream lengths never retrigger XLA compilation -- one
#: compile per (vmap layout, port/bucket variant, batch size, share-pad).
CHUNK = 16384


@functools.lru_cache(maxsize=8)
def _sim_chunk_fn(port_model: bool, emit_ends: bool = False):
    """Build the raw (unjitted) per-instruction scan program.

    Returns ``sim_chunk(carry, xs, idx, design, bucket)``: one
    ``lax.scan`` over a chunk of compiled-trace columns, threading the
    15-slot timing carry.  :func:`_jax_fns` wraps it in the two jitted
    vmap layouts; :mod:`repro.multicore.jitarb` embeds it directly inside
    its whole-trace arbitration program (vmapping and jitting itself), so
    the scheduling arithmetic lives in exactly one place.
    """
    import jax.numpy as jnp
    from jax import lax

    def f64(v):
        return jnp.asarray(v, dtype=jnp.float64)

    def sim_chunk(carry0, xs, idx, design, bucket):
        (wl, fs, dr, issue, load_lat, wlbp, wls, pipe) = design
        (shares, n_shares, E, tail, burst, sched_end, charge_store,
         store_free, inv_store, inv_load) = bucket
        S = shares.shape[0]
        # XLA:CPU contracts ``tk + rate * dt`` into a fused multiply-add
        # (one rounding), while the numpy/reference token bucket rounds the
        # product first -- a 1-ulp drift that breaks oracle parity.  A
        # select on a runtime-only predicate pins the product: neither the
        # HLO simplifier (the predicate is unknown) nor LLVM's instruction
        # selector (the add's operand is a select, not the multiply) can
        # re-fuse it.
        rt_true = E == E

        def unfused(x):
            return lax.select(rt_true, x, jnp.zeros_like(x))

        def share_at(t):
            e = jnp.floor(t / E)
            i = jnp.clip(e, 0.0, S - 1.0).astype(jnp.int32)
            return jnp.where(e < n_shares, shares[i], tail)

        def advance(tokens, bt, t):
            def cond(s):
                return s[1] < t

            def body(s):
                tk, b = s
                rate = share_at(b)
                e_end = (jnp.floor(b / E) + 1.0) * E
                step_end = jnp.where(b >= sched_end, t,
                                     jnp.minimum(t, e_end))
                tk = jnp.where(jnp.isinf(rate), burst,
                               jnp.minimum(burst,
                                           tk + unfused(rate
                                                        * (step_end - b))))
                return tk, step_end

            # a saturated bucket stays saturated: every refill step clamps
            # ``min(burst, burst + rate*dt)`` with rate >= 0 back to burst,
            # so jump straight to ``t`` without walking the epochs
            bt = jnp.where(tokens >= burst, jnp.maximum(bt, t), bt)
            return lax.while_loop(cond, body, (tokens, bt))

        def grant_bucket(tokens, bt, t_earliest, n_bytes, want):
            # ``want=False`` pins every walk to its start (zero iterations)
            # so ops that discard the grant -- rasa_mm, uncharged stores --
            # don't spin the bucket up to their issue time for nothing.
            # Wanting lanes see bit-identical arithmetic either way.
            tokens, bt = advance(tokens, bt,
                                 jnp.where(want, t_earliest, bt))
            need = jnp.minimum(n_bytes, burst)

            def cond(s):
                return ~s[3]

            def body(s):
                t, tk, start, done = s
                rate = share_at(t)
                infr = jnp.isinf(rate)
                e_end = (jnp.floor(t / E) + 1.0) * E
                t_hit = t + (need - tk) / rate
                hit = (rate > 0.0) & ((t_hit <= e_end) | (t >= sched_end))
                dead = ~infr & (rate <= 0.0) & (t >= sched_end)
                fin = infr | hit | dead
                start2 = jnp.where(infr, t,
                                   jnp.where(dead, jnp.inf, t_hit))
                tk2 = jnp.where(rate > 0.0,
                                tk + unfused(rate * (e_end - t)), tk)
                return (jnp.where(fin, t, e_end), jnp.where(fin, tk, tk2),
                        jnp.where(fin, start2, start), fin)

            # when the bucket already covers the request the walk's result
            # is discarded below -- don't spin it
            walked = lax.while_loop(
                cond, body, (bt, tokens, f64(0.0),
                             ~want | (tokens >= need)))[2]
            start = jnp.where(tokens >= need, t_earliest,
                              jnp.maximum(walked, t_earliest))
            start = jnp.where(want, start, bt)
            tokens, bt = advance(tokens, bt, start)
            return start, tokens - n_bytes, bt

        def grant_port(tokens, bt, t_earliest, n_bytes, want):
            # infinite tail share, empty schedule: every request is granted
            # the moment the port frees up, the bucket state is inert.
            return t_earliest, tokens, bt

        grant = grant_port if port_model else grant_bucket

        def step(carry, x):
            (reg_ready, pffs, pffe, pfse, pdre, have_prev, wlfree, t_end,
             wl_skips, bw_stall, next_free, snext, last_grant,
             tokens, bt) = carry
            # pre-step outputs: at an OP_END marker these are the results of
            # the lane's just-finished packed segment
            emit = (t_end, wl_skips, bw_stall, last_grant) if emit_ends \
                else None
            op, rdst, ra, rb, nb, tm_i, reus, i = x
            t_issue = i / issue
            is_tl = op == OP_TL
            is_ts = op == OP_TS
            is_mm = op == OP_MM

            rr_rd = reg_ready[rdst]
            rr_ra = reg_ready[ra]
            rr_rb = reg_ready[rb]

            # ---- memory path (TL / TS share one masked grant) -------------
            port_start_tl = jnp.maximum(t_issue, next_free)
            t_avail = jnp.maximum(t_issue, rr_ra)
            port_start_ts = jnp.maximum(t_avail, snext)
            req = jnp.where(is_tl, port_start_tl, port_start_ts)
            do_grant = is_tl | (is_ts & jnp.logical_and(
                charge_store, jnp.logical_not(store_free)))
            gstart, gtokens, gbt = grant(tokens, bt, req, nb, do_grant)
            tokens = jnp.where(do_grant, gtokens, tokens)
            bt = jnp.where(do_grant, gbt, bt)
            start_mem = jnp.where(do_grant, gstart, req)
            done_tl = start_mem + load_lat
            next_free = jnp.where(is_tl, start_mem + inv_load, next_free)
            ts_tracked = is_ts & jnp.logical_not(store_free)
            snext = jnp.where(ts_tracked, start_mem + inv_store, snext)
            start_ts = jnp.where(store_free, t_avail, start_mem)
            stall = jnp.where(
                is_tl, start_mem - port_start_tl,
                jnp.where(ts_tracked, start_mem - port_start_ts, 0.0))
            bw_stall = bw_stall + stall
            last_grant = jnp.where(is_tl | ts_tracked,
                                   jnp.maximum(last_grant, start_mem),
                                   last_grant)

            # ---- rasa_mm scheduling rules ---------------------------------
            t_ready_ac = jnp.maximum(t_issue, jnp.maximum(rr_ra, rr_rd))
            t_ready_b = jnp.maximum(t_issue, rr_rb)
            reuse = wlbp & reus
            pffs_e = jnp.where(have_prev, pffs, 0.0)
            pffe_e = jnp.where(have_prev, pffe, 0.0)
            pfse_e = jnp.where(have_prev, pfse, 0.0)
            pdre_e = jnp.where(have_prev, pdre, 0.0)

            ff_reuse = jnp.maximum(t_ready_ac, pffe_e)

            wls_wl = jnp.maximum(jnp.maximum(t_ready_b, pffs_e), wlfree)
            hidden = have_prev & (wls_wl <= pfse)
            w_ready = jnp.where(hidden, wls_wl + 1.0, wls_wl + wl)
            ff_wls = jnp.maximum(jnp.maximum(t_ready_ac, pffe_e), w_ready)

            pipe_wl = jnp.maximum(jnp.maximum(t_ready_b, pfse_e), wlfree)
            ff_pipe = jnp.maximum(jnp.maximum(t_ready_ac, pipe_wl + wl),
                                  pdre_e)

            base_wl = jnp.maximum(jnp.maximum(t_ready_b, pdre_e), wlfree)
            ff_base = jnp.maximum(t_ready_ac, base_wl + wl)

            wl_start = jnp.where(wls, wls_wl,
                                 jnp.where(pipe, pipe_wl, base_wl))
            ff_start = jnp.where(
                reuse, ff_reuse,
                jnp.where(wls, ff_wls, jnp.where(pipe, ff_pipe, ff_base)))

            ff_end = ff_start + tm_i
            fs_end = ff_end + fs
            dr_end = fs_end + dr

            # ---- merge ----------------------------------------------------
            new_reg = jnp.where(is_tl, done_tl, dr_end)
            writes = is_tl | is_mm
            reg_ready = reg_ready.at[rdst].set(
                jnp.where(writes, new_reg, rr_rd))
            contrib = jnp.where(
                is_tl, done_tl,
                jnp.where(is_ts, start_ts + 1.0,
                          jnp.where(is_mm, dr_end, -jnp.inf)))
            t_end = jnp.maximum(t_end, contrib)
            pffs = jnp.where(is_mm, ff_start, pffs)
            pffe = jnp.where(is_mm, ff_end, pffe)
            pfse = jnp.where(is_mm, fs_end, pfse)
            pdre = jnp.where(is_mm, dr_end, pdre)
            have_prev = have_prev | is_mm
            wlfree = jnp.where(is_mm & ~reuse, wl_start + wl, wlfree)
            wl_skips = wl_skips + (is_mm & reuse).astype(jnp.int32)

            new_carry = (reg_ready, pffs, pffe, pfse, pdre, have_prev,
                         wlfree, t_end, wl_skips, bw_stall, next_free,
                         snext, last_grant, tokens, bt)
            if emit_ends:
                # OP_END: reset the lane for its next packed segment
                is_end = op == OP_END

                def rst(val, init):
                    return jnp.where(is_end, init, val)

                new_carry = (jnp.where(is_end, 0.0, reg_ready),
                             rst(pffs, -1.0), rst(pffe, 0.0), rst(pfse, 0.0),
                             rst(pdre, 0.0), rst(have_prev, False),
                             rst(wlfree, 0.0), rst(t_end, 0.0),
                             rst(wl_skips, 0), rst(bw_stall, 0.0),
                             rst(next_free, 0.0), rst(snext, 0.0),
                             rst(last_grant, 0.0), rst(tokens, burst),
                             rst(bt, 0.0))
            return new_carry, emit

        final, ys = lax.scan(step, carry0, (xs[0], xs[1], xs[2], xs[3],
                                            xs[4], xs[5], xs[6], idx),
                             unroll=8)
        return final, ys

    return sim_chunk


#: bucket in_axes of the two vmap layouts below (and of
#: ``multicore.jitarb``'s in-program lane vmap ``_B_LANES``, which
#: extends ``_B_CORES`` by also mapping inv_load / inv_store per lane so
#: heterogeneous core mixes trace through one program): the cores layout
#: maps shares / n_shares / tail / sched_end per lane, everything else
#: is shared.
_B_SWEEP = ((None,) * 9) + (0,)          # bucket: inv_load per design
_B_CORES = (0, 0, None, 0, None, 0) + ((None,) * 4)


@functools.lru_cache(maxsize=8)
def _jax_fns(port_model: bool, emit_ends: bool = False):
    import jax

    sim_chunk = _sim_chunk_fn(port_model, emit_ends)
    # two vmap layouts: `sweep` shares one trace across design lanes (the
    # shared xs keeps every per-step op a cheap scalar-indexed slice);
    # `cores` gives each lane its own trace under one shared design --
    # with the share schedule per lane (shares / n_shares / tail /
    # sched_end), which is what weighted epoch arbitration produces.
    sweep = jax.jit(jax.vmap(sim_chunk, in_axes=(0, None, None, 0, _B_SWEEP)))
    cores = jax.jit(jax.vmap(sim_chunk, in_axes=(0, 0, None, None, _B_CORES)))
    return sweep, cores


#: carry slots read back after the last chunk (see ``sim_chunk``):
#: t_end, wl_skips, bw_stall, last_grant.
_OUT_SLOTS = (7, 8, 9, 12)


def _init_carry(n_lanes: int, burst: float):
    import jax.numpy as jnp
    f = np.float64
    z = np.zeros(n_lanes, dtype=f)
    return (jnp.asarray(np.zeros((n_lanes, NUM_TREGS), dtype=f)),
            jnp.asarray(np.full(n_lanes, -1.0, dtype=f)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(z),
            jnp.asarray(np.zeros(n_lanes, dtype=bool)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(np.zeros(n_lanes, dtype=np.int32)),
            jnp.asarray(z), jnp.asarray(z), jnp.asarray(z), jnp.asarray(z),
            jnp.asarray(np.full(n_lanes, burst, dtype=f)), jnp.asarray(z))


def _host_bytes(tree) -> int:
    """Bytes of the numpy leaves of ``tree``: what a jitted call copies
    from the host to the device."""
    import jax
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, (np.ndarray, np.generic)))


def _run_chunked(fn, carry, trace_chunks, idx_chunks, design, bucket,
                 useful, pick=None):
    """Thread the batched carry through one jitted chunk call per chunk.

    ``useful`` is the number of real (instruction, lane) steps among the
    scanned ones, for the ``sim.useful_steps`` counter.  ``pick`` (one int
    array per chunk) selects per-step emission positions to keep -- the
    OP_END markers of a packed stream.  Only those slices are retained
    (lazily), so the chunk chain stays async and the full [B, L] emission
    buffers are never materialized on the host.
    """
    kept = []
    n_chunks = len(trace_chunks)
    # the initial carry was copied from the host when it was made
    h2d = sum(c.nbytes for c in carry) + sum(
        _host_bytes((xs, idx, design, bucket))
        for xs, idx in zip(trace_chunks, idx_chunks))
    with probe.span("sim.dispatch", chunk_calls=n_chunks,
                    scan_steps=n_chunks * CHUNK * len(carry[7]),
                    useful_steps=useful, h2d_bytes=h2d):
        for k, (xs, idx) in enumerate(zip(trace_chunks, idx_chunks)):
            carry, ys = fn(carry, xs, idx, design, bucket)
            if pick is not None and len(pick[k]):
                kept.append(tuple(y[..., pick[k]] for y in ys))
    with probe.span("sim.wait"):
        outs = [np.asarray(carry[s]) for s in _OUT_SLOTS]
        if pick is None:
            return outs
        if not kept:
            empty = np.zeros((0,))
            return outs, [empty] * len(_OUT_SLOTS)
        cat = [np.concatenate([np.asarray(y[k]) for y in kept], axis=-1)
               for k in range(len(_OUT_SLOTS))]
    return outs, cat


def _xs_arrays(trace: CompiledTrace):
    return (trace.opcode, trace.r_dst, trace.r_a, trace.r_b, trace.nbytes,
            trace.tm, trace.reusable)


def _empty_trace() -> CompiledTrace:
    i32, f = np.int32, np.float64
    z = np.zeros(0, dtype=i32)
    return CompiledTrace(opcode=z, r_dst=z, r_a=z, r_b=z,
                         nbytes=np.zeros(0, dtype=f),
                         tm=np.zeros(0, dtype=f), macs=np.zeros(0, dtype=f),
                         reusable=np.zeros(0, dtype=bool),
                         n_tl=0, n_ts=0, n_mm=0, useful_macs=0.0)


def _chunk_single(trace: CompiledTrace, idx: np.ndarray | None = None):
    """Chunk one trace: list of per-chunk xs tuples + f64 index arrays.

    ``idx`` overrides the instruction-index array (packed streams restart
    issue indices per segment); by default it is ``arange(len)``.
    """
    n_chunks = max(1, -(-len(trace) // CHUNK))
    L = n_chunks * CHUNK
    padded = trace.padded(L)
    arrays = _xs_arrays(padded)
    if idx is not None:
        idx_full = np.zeros(L, dtype=np.float64)
        idx_full[:len(idx)] = idx
    chunks, idxs = [], []
    for k in range(n_chunks):
        sl = slice(k * CHUNK, (k + 1) * CHUNK)
        chunks.append(tuple(a[sl] for a in arrays))
        idxs.append(np.arange(sl.start, sl.stop, dtype=np.float64)
                    if idx is None else idx_full[sl])
    return chunks, idxs


def _chunk_batch(traces: Sequence[CompiledTrace]):
    """Chunk a batch of traces to a common length: xs leaves are [B, CHUNK]."""
    n_chunks = max(1, -(-max(len(t) for t in traces) // CHUNK))
    padded = [t.padded(n_chunks * CHUNK) for t in traces]
    per_trace = [_xs_arrays(t) for t in padded]
    chunks, idxs = [], []
    for k in range(n_chunks):
        sl = slice(k * CHUNK, (k + 1) * CHUNK)
        chunks.append(tuple(np.stack([arrs[f][sl] for arrs in per_trace])
                            for f in range(7)))
        idxs.append(np.arange(sl.start, sl.stop, dtype=np.float64))
    return chunks, idxs


def _design_arrays(cfgs: Sequence[EngineConfig]):
    f = np.float64
    return (np.array([c.wl_cycles for c in cfgs], dtype=f),
            np.array([c.fs_cycles for c in cfgs], dtype=f),
            np.array([c.dr_cycles for c in cfgs], dtype=f),
            np.array([c.core_issue_width * (c.core_clock_hz
                                            / c.engine_clock_hz)
                      for c in cfgs], dtype=f),
            np.array([float(c.load_latency) for c in cfgs], dtype=f),
            np.array([c.wlbp for c in cfgs], dtype=bool),
            np.array([c.wls for c in cfgs], dtype=bool),
            np.array([c.pipe for c in cfgs], dtype=bool))


def _design_scalars(cfg: EngineConfig):
    return (np.float64(cfg.wl_cycles), np.float64(cfg.fs_cycles),
            np.float64(cfg.dr_cycles),
            np.float64(cfg.core_issue_width * (cfg.core_clock_hz
                                               / cfg.engine_clock_hz)),
            np.float64(cfg.load_latency), bool(cfg.wlbp), bool(cfg.wls),
            bool(cfg.pipe))


def _bucket_arrays(params: StreamModelParams, inv_load, tail,
                   pad_to: int | None = None):
    """The bucket tuple consumed by ``sim_chunk`` -- the single place its
    field order lives; ``inv_load`` is an array for design sweeps,
    ``tail`` an array for core batches.  ``pad_to`` overrides the share
    padding (per-lane stacking needs a common length)."""
    S = pad_to if pad_to is not None else _pow2(max(1, len(params.shares)),
                                                lo=4)
    shares = np.zeros(S, dtype=np.float64)
    if params.shares:
        shares[:len(params.shares)] = params.shares
    store_free = params.store_ports is None
    inv_store = 1.0 / params.store_ports if not store_free else 1.0
    return (shares, np.float64(len(params.shares)),
            np.float64(params.epoch_cycles), tail,
            np.float64(params.burst_bytes), np.float64(params.schedule_end),
            bool(params.charge_store_bytes), bool(store_free),
            np.float64(inv_store), inv_load)


#: bucket fields the cores layout maps per lane (must mirror the
#: ``_B_CORES`` in_axes in ``_jax_fns``): shares, n_shares, tail,
#: sched_end.
_BUCKET_LANE_FIELDS = (0, 1, 3, 5)


def _bucket_arrays_per_lane(params_list: Sequence[StreamModelParams],
                            inv_load):
    """Stack per-lane bucket rows for the cores layout.

    Each lane's row is built by :func:`_bucket_arrays` (so the field
    layout lives once); the fields ``_B_CORES`` vmaps are stacked, the
    rest come from lane 0 (``run_cores`` groups lanes so they agree).
    """
    S = _pow2(max(1, max(len(p.shares) for p in params_list)), lo=4)
    rows = [_bucket_arrays(p, inv_load, np.float64(p.tail_share), pad_to=S)
            for p in params_list]
    return tuple(
        np.stack([row[k] for row in rows]) if k in _BUCKET_LANE_FIELDS
        else rows[0][k]
        for k in range(len(rows[0])))


# --------------------------------------------------------------------------
# MM-only port-model path: compile the memory behaviour into the trace
# --------------------------------------------------------------------------
#
# Under the paper's idealized port model the tile-load stream never couples
# back into the compute recurrence: TL grant times are the running-max
# recurrence  start_k = max(t_issue_k, start_{k-1} + 1/ports),  solvable in
# closed form (max-accumulate) with numpy, and a free store's finish time is
# max(t_issue, producer's DR end) + 1 where the producer of the stored
# register is statically known.  Only the rasa_mm scheduling recurrence is
# genuinely sequential -- so the scan runs over MM rows alone (roughly half
# the stream) with a step that has no arbiter state at all.  This is the
# design-sweep fast path; the token-bucket models keep the full-stream scan.

@dataclasses.dataclass(frozen=True, eq=False)
class _MMAnalysis:
    """Design-independent static analysis of a trace's dataflow."""

    mm_pos: np.ndarray      # [n_mm] stream position (issue index)
    c: np.ndarray           # int32 register ids
    a: np.ndarray
    b: np.ndarray
    #: per-operand last-writer kind: 0 = never written, 1 = TL, 2 = MM
    a_kind: np.ndarray
    b_kind: np.ndarray
    c_kind: np.ndarray
    #: TL ordinal of the writer when kind == 1
    a_tl: np.ndarray
    b_tl: np.ndarray
    c_tl: np.ndarray
    reusable: np.ndarray
    tm: np.ndarray
    #: max stream position of free stores whose producer is MM m (-1: none)
    ts_max_pos: np.ndarray  # [n_mm]
    tl_pos: np.ndarray      # [n_tl] stream positions of TLs
    #: free stores with a static (TL / never-written) source: position,
    #: kind and TL ordinal
    ts_const_pos: np.ndarray
    ts_const_kind: np.ndarray
    ts_const_tl: np.ndarray


def _resolve_writers(wr_pos: dict[int, np.ndarray], is_tl: np.ndarray,
                     tl_ord: np.ndarray, mm_ord: np.ndarray,
                     read_pos: np.ndarray, read_reg: np.ndarray):
    """Last writer strictly before each read: (kind, tl ordinal, mm ordinal).

    kind: 0 = never written, 1 = TL, 2 = MM.
    """
    kind = np.zeros(len(read_pos), dtype=np.int8)
    tl_i = np.zeros(len(read_pos), dtype=np.int32)
    mm_i = np.zeros(len(read_pos), dtype=np.int32)
    for reg, wpos in wr_pos.items():
        mask = read_reg == reg
        if not mask.any() or not len(wpos):
            continue
        k = np.searchsorted(wpos, read_pos[mask], side="left") - 1
        wj = wpos[np.clip(k, 0, None)]
        has = k >= 0
        w_is_tl = is_tl[wj]
        kind[mask] = np.where(has, np.where(w_is_tl, 1, 2), 0)
        tl_i[mask] = np.where(has & w_is_tl, tl_ord[wj], 0)
        mm_i[mask] = np.where(has & ~w_is_tl, mm_ord[wj], 0)
    return kind, tl_i, mm_i


_MM_CACHE = None  # type: ignore[assignment]


def _mm_analysis(trace: CompiledTrace) -> _MMAnalysis:
    global _MM_CACHE
    if _MM_CACHE is None:
        import weakref
        _MM_CACHE = weakref.WeakKeyDictionary()
    hit = _MM_CACHE.get(trace)
    if hit is not None:
        return hit
    op = trace.opcode
    is_tl = op == OP_TL
    is_ts = op == OP_TS
    is_mm = op == OP_MM
    pos = np.arange(len(op), dtype=np.int64)
    tl_ord = (np.cumsum(is_tl) - 1).astype(np.int32)
    mm_ord = (np.cumsum(is_mm) - 1).astype(np.int32)
    writes = is_tl | is_mm
    wr_pos = {reg: pos[writes & (trace.r_dst == reg)]
              for reg in range(NUM_TREGS)}

    mm_pos = pos[is_mm]
    c = trace.r_dst[is_mm]
    a = trace.r_a[is_mm]
    b = trace.r_b[is_mm]
    a_kind, a_tl, _ = _resolve_writers(wr_pos, is_tl, tl_ord, mm_ord,
                                       mm_pos, a)
    b_kind, b_tl, _ = _resolve_writers(wr_pos, is_tl, tl_ord, mm_ord,
                                       mm_pos, b)
    c_kind, c_tl, _ = _resolve_writers(wr_pos, is_tl, tl_ord, mm_ord,
                                       mm_pos, c)

    ts_pos = pos[is_ts]
    ts_src = trace.r_a[is_ts]
    t_kind, t_tl, t_mm = _resolve_writers(wr_pos, is_tl, tl_ord, mm_ord,
                                          ts_pos, ts_src)
    n_mm = int(is_mm.sum())
    ts_max_pos = np.full(n_mm, -1, dtype=np.int64)
    dyn = t_kind == 2
    if dyn.any():
        np.maximum.at(ts_max_pos, t_mm[dyn], ts_pos[dyn])
    out = _MMAnalysis(
        mm_pos=mm_pos, c=c, a=a, b=b,
        a_kind=a_kind, b_kind=b_kind, c_kind=c_kind,
        a_tl=a_tl, b_tl=b_tl, c_tl=c_tl,
        reusable=trace.reusable[is_mm], tm=trace.tm[is_mm],
        ts_max_pos=ts_max_pos, tl_pos=pos[is_tl],
        ts_const_pos=ts_pos[~dyn], ts_const_kind=t_kind[~dyn],
        ts_const_tl=t_tl[~dyn])
    _MM_CACHE[trace] = out
    return out


@functools.lru_cache(maxsize=1)
def _jax_mm_fn():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def sim_chunk(carry0, xs, design):
        (wl, fs, dr, wlbp, wls, pipe) = design

        def step(carry, x):
            (reg_ready, pffs, pffe, pfse, pdre, have_prev, wlfree, t_end,
             wl_skips) = carry
            (valid, c, a, b, a_dyn, b_dyn, c_dyn, a_const, b_const, c_const,
             reus, tm_i, t_issue, ts_mask, ts_issue) = x
            ra_v = jnp.where(a_dyn, reg_ready[a], a_const)
            rb_v = jnp.where(b_dyn, reg_ready[b], b_const)
            rc_v = jnp.where(c_dyn, reg_ready[c], c_const)
            t_ready_ac = jnp.maximum(t_issue, jnp.maximum(ra_v, rc_v))
            t_ready_b = jnp.maximum(t_issue, rb_v)
            reuse = wlbp & reus
            pffs_e = jnp.where(have_prev, pffs, 0.0)
            pffe_e = jnp.where(have_prev, pffe, 0.0)
            pfse_e = jnp.where(have_prev, pfse, 0.0)
            pdre_e = jnp.where(have_prev, pdre, 0.0)

            ff_reuse = jnp.maximum(t_ready_ac, pffe_e)
            wls_wl = jnp.maximum(jnp.maximum(t_ready_b, pffs_e), wlfree)
            hidden = have_prev & (wls_wl <= pfse)
            w_ready = jnp.where(hidden, wls_wl + 1.0, wls_wl + wl)
            ff_wls = jnp.maximum(jnp.maximum(t_ready_ac, pffe_e), w_ready)
            pipe_wl = jnp.maximum(jnp.maximum(t_ready_b, pfse_e), wlfree)
            ff_pipe = jnp.maximum(jnp.maximum(t_ready_ac, pipe_wl + wl),
                                  pdre_e)
            base_wl = jnp.maximum(jnp.maximum(t_ready_b, pdre_e), wlfree)
            ff_base = jnp.maximum(t_ready_ac, base_wl + wl)
            wl_start = jnp.where(wls, wls_wl,
                                 jnp.where(pipe, pipe_wl, base_wl))
            ff_start = jnp.where(
                reuse, ff_reuse,
                jnp.where(wls, ff_wls, jnp.where(pipe, ff_pipe, ff_base)))

            ff_end = ff_start + tm_i
            fs_end = ff_end + fs
            dr_end = fs_end + dr
            ts_c = jnp.where(ts_mask, jnp.maximum(ts_issue, dr_end) + 1.0,
                             -jnp.inf)
            upd = (reg_ready.at[c].set(jnp.where(valid, dr_end,
                                                 reg_ready[c])),
                   jnp.where(valid, ff_start, pffs),
                   jnp.where(valid, ff_end, pffe),
                   jnp.where(valid, fs_end, pfse),
                   jnp.where(valid, dr_end, pdre),
                   have_prev | valid,
                   jnp.where(valid & ~reuse, wl_start + wl, wlfree),
                   jnp.where(valid,
                             jnp.maximum(t_end, jnp.maximum(dr_end, ts_c)),
                             t_end),
                   wl_skips + (valid & reuse).astype(jnp.int32))
            return upd, None

        final, _ = lax.scan(step, carry0, xs, unroll=8)
        return final

    _DESIGN_AXES = (0, 0, 0, 0, 0, 0)
    return jax.jit(jax.vmap(sim_chunk, in_axes=(0, None, _DESIGN_AXES)))


def _mm_init_carry(n_lanes: int):
    import jax.numpy as jnp
    f = np.float64
    z = np.zeros(n_lanes, dtype=f)
    return (jnp.asarray(np.zeros((n_lanes, NUM_TREGS), dtype=f)),
            jnp.asarray(np.full(n_lanes, -1.0, dtype=f)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(z),
            jnp.asarray(np.zeros(n_lanes, dtype=bool)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(np.zeros(n_lanes, dtype=np.int32)))


def _load_sig(cfg: EngineConfig, params: StreamModelParams | None):
    ports = params.load_ports if params is not None else cfg.load_ports
    issue = cfg.core_issue_width * (cfg.core_clock_hz / cfg.engine_clock_hz)
    return (issue, ports, float(cfg.load_latency))


def _port_static(ana: _MMAnalysis, sig) -> tuple[np.ndarray, float]:
    """Per load-signature: TL done times + the static part of ``cycles``."""
    issue, ports, load_lat = sig
    inv = 1.0 / ports
    t_issue_tl = ana.tl_pos / issue
    if len(t_issue_tl):
        drift = np.arange(len(t_issue_tl), dtype=np.float64) * inv
        start = np.maximum.accumulate(t_issue_tl - drift) + drift
        done_tl = start + load_lat
        static_end = float(done_tl.max())
    else:
        done_tl = np.zeros(0, dtype=np.float64)
        static_end = 0.0
    if len(ana.ts_const_pos):
        ready = np.where(ana.ts_const_kind == 1,
                         done_tl[ana.ts_const_tl] if len(done_tl)
                         else 0.0, 0.0)
        contrib = np.maximum(ana.ts_const_pos / issue, ready) + 1.0
        static_end = max(static_end, float(contrib.max()))
    return done_tl, static_end


def _sweep_port_mm(trace: CompiledTrace, cfgs: Sequence[EngineConfig],
                   params: StreamModelParams | None) -> list[TimingResult]:
    """The MM-only jax sweep (see section comment above)."""
    with probe.span("sim.analyse"):
        ana = _mm_analysis(trace)
        groups: dict[tuple, list[int]] = {}
        for j, cfg in enumerate(cfgs):
            groups.setdefault(_load_sig(cfg, params), []).append(j)
    n_mm = len(ana.mm_pos)
    results: list[TimingResult | None] = [None] * len(cfgs)
    fn = _jax_mm_fn()
    for sig, members in groups.items():
        with probe.span("sim.analyse"):
            done_tl, static_end = _port_static(ana, sig)
        if n_mm == 0:
            with probe.span("sim.report"):
                for j in members:
                    results[j] = _result(trace, cfgs[j], static_end, 0, 0.0)
            continue
        n_chunks = -(-n_mm // CHUNK)
        B = _pow2(len(members), lo=1)
        with x64():
            with probe.span("sim.stage"):
                cols = _mm_columns(ana, done_tl, sig[0], n_chunks * CHUNK)
                mem_cfgs = [cfgs[j] for j in members]
                d = _design_arrays(mem_cfgs
                                   + [mem_cfgs[-1]] * (B - len(mem_cfgs)))
                # wl fs dr wlbp wls pipe
                design = (d[0], d[1], d[2], d[5], d[6], d[7])
                carry = _mm_init_carry(B)
            # every chunk call copies its column slices and the designs
            per_call = (sum(c.itemsize for c in cols) * CHUNK
                        + _host_bytes(design))
            with probe.span("sim.dispatch", chunk_calls=n_chunks,
                            scan_steps=n_chunks * CHUNK * B,
                            useful_steps=n_mm * len(members),
                            h2d_bytes=(sum(c.nbytes for c in carry)
                                       + n_chunks * per_call)):
                for k in range(n_chunks):
                    sl = slice(k * CHUNK, (k + 1) * CHUNK)
                    carry = fn(carry, tuple(col[sl] for col in cols), design)
            with probe.span("sim.wait"):
                t_end = np.asarray(carry[7])
                skips = np.asarray(carry[8])
        with probe.span("sim.report"):
            for bi, j in enumerate(members):
                results[j] = _result(trace, cfgs[j],
                                     max(float(t_end[bi]), static_end),
                                     int(skips[bi]), 0.0)
    return results  # type: ignore[return-value]


def _mm_columns(ana: _MMAnalysis, done_tl: np.ndarray, issue: float,
                L: int) -> tuple[np.ndarray, ...]:
    """The 15 per-MM scan columns of one load-signature group, padded
    with invalid steps to ``L``."""
    n_mm = len(ana.mm_pos)

    def const_of(kind, tl_idx):
        if len(done_tl):
            v = done_tl[tl_idx]
        else:
            v = np.zeros(len(tl_idx), dtype=np.float64)
        return np.where(kind == 1, v, 0.0)

    def padded(arr, fill=0):
        return np.concatenate([arr, np.full(L - n_mm, fill, dtype=arr.dtype)])

    f64 = np.float64
    return (padded(np.ones(n_mm, dtype=bool)),
            padded(ana.c), padded(ana.a), padded(ana.b),
            padded(ana.a_kind == 2), padded(ana.b_kind == 2),
            padded(ana.c_kind == 2),
            padded(const_of(ana.a_kind, ana.a_tl).astype(f64)),
            padded(const_of(ana.b_kind, ana.b_tl).astype(f64)),
            padded(const_of(ana.c_kind, ana.c_tl).astype(f64)),
            padded(ana.reusable), padded(ana.tm),
            padded((ana.mm_pos / issue).astype(f64)),
            padded(ana.ts_max_pos >= 0),
            padded(np.where(ana.ts_max_pos >= 0,
                            ana.ts_max_pos / issue, 0.0).astype(f64)))


def sweep_trace(trace: CompiledTrace, cfgs: Sequence[EngineConfig],
                params: StreamModelParams | None = None,
                backend: str = "fast") -> list[TimingResult]:
    """Simulate one compiled trace under many engine configs at once.

    With ``params=None`` each config gets the paper's idealized port model
    (``LoadStreamModel(cfg.load_ports)``); an explicit ``params`` applies
    to every config.
    """
    if not cfgs:
        return []
    # a single design lane cannot amortize the vmapped scan: "fast" keeps
    # one-off simulations on the numpy loop (explicit "jax" still honored)
    work = len(trace) * len(cfgs) if len(cfgs) > 1 else 0
    concrete = resolve_backend(backend, work)
    if concrete == "numpy":
        return [_run_numpy_params(
                    trace, cfg,
                    params or StreamModelParams.for_config(cfg))[0]
                for cfg in cfgs]

    base = params or StreamModelParams(load_ports=1)
    if base.is_port_model and base.store_ports is None:
        return _sweep_port_mm(trace, cfgs, params)
    sweep_fn = _jax_fns(base.is_port_model)[0]
    # pad the design batch to a power of two so neighbourhood sweeps of any
    # size reuse the same compiled executable
    n = len(cfgs)
    cfgs_p = list(cfgs) + [cfgs[-1]] * (_pow2(n, lo=1) - n)
    chunks, idxs = _chunk_single(trace)
    inv_load = np.array(
        [1.0 / (params.load_ports if params is not None else c.load_ports)
         for c in cfgs_p], dtype=np.float64)
    bucket = _bucket_arrays(base, inv_load, np.float64(base.tail_share))
    with x64():
        carry = _init_carry(len(cfgs_p), base.burst_bytes)
        t_end, skips, stall, _ = _run_chunked(
            sweep_fn, carry, chunks, idxs, _design_arrays(cfgs_p), bucket,
            len(trace) * n)
    return [_result(trace, cfg, t_end[b], skips[b], stall[b])
            for b, cfg in enumerate(cfgs)]


def run_cores(traces: Sequence[CompiledTrace],
              cfg: EngineConfig | Sequence[EngineConfig],
              params: Sequence[StreamModelParams],
              backend: str = "fast") -> list[tuple[TimingResult, float]]:
    """Simulate one trace per core.

    ``cfg`` is one engine config shared by every core, or one per core
    (heterogeneous chips).  ``params[i]`` describes core *i*'s arbiter;
    schedules may differ per core in both ``shares`` and ``tail_share`` --
    exactly what weighted epoch arbitration produces.  Returns
    ``(TimingResult, last_grant)`` per core; ``last_grant`` is the activity
    horizon the chip-level relaxation reads back.
    """
    if len(traces) != len(params):
        raise ValueError("need one StreamModelParams per trace")
    if not traces:
        return []
    cfgs = [cfg] * len(traces) if isinstance(cfg, EngineConfig) else list(cfg)
    if len(cfgs) != len(traces):
        raise ValueError("need one EngineConfig per trace (or a single "
                         "shared one)")
    # a vmapped call can only span batch-compatible lanes -- same engine
    # config and bucket *shape* (port vs. bucket model, epoch length,
    # burst, store accounting); shares/tails vary per lane.
    groups: dict[tuple, list[int]] = {}
    for i, (c, p) in enumerate(zip(cfgs, params)):
        key = (c, p.is_port_model, p.epoch_cycles, p.burst_bytes,
               p.charge_store_bytes, p.load_ports, p.store_ports)
        groups.setdefault(key, []).append(i)
    out: list[tuple[TimingResult, float] | None] = [None] * len(traces)
    for idxs in groups.values():
        # the per-core layout cannot share instruction arrays across
        # lanes, so its scan step is gather-bound and only beats the
        # inlined numpy loop on large batches -- "fast" stays on numpy
        # below that scale (and always for one lane, which cannot
        # amortize the vmap at all).  Resolved per *group*: a mixed chip
        # whose cores split into small per-design groups must not pay one
        # unamortized vmapped scan per group.
        total = sum(len(traces[i]) for i in idxs) if len(idxs) > 1 else 0
        concrete = resolve_backend(
            backend, total if total >= FAST_JAX_MIN_CORES_INSTRS else 0)
        if concrete == "numpy":
            for i in idxs:
                out[i] = _run_numpy_params(traces[i], cfgs[i], params[i])
        else:
            res = _run_cores_jax([traces[i] for i in idxs], cfgs[idxs[0]],
                                 [params[i] for i in idxs])
            for i, r in zip(idxs, res):
                out[i] = r
    return out  # type: ignore[return-value]


def _run_cores_jax(traces: Sequence[CompiledTrace], cfg: EngineConfig,
                   params: Sequence[StreamModelParams]
                   ) -> list[tuple[TimingResult, float]]:
    """The jax cores layout for one batch-compatible lane group."""
    head = params[0]
    cores_fn = _jax_fns(head.is_port_model)[1]
    n = len(traces)
    lanes = list(traces) + [_empty_trace()] * (_pow2(n, lo=1) - n)
    pad_p = list(params) + [head] * (len(lanes) - n)
    bucket = _bucket_arrays_per_lane(pad_p,
                                     np.float64(1.0 / head.load_ports))
    chunks, idxs = _chunk_batch(lanes)
    with x64():
        carry = _init_carry(len(lanes), head.burst_bytes)
        t_end, skips, stall, lg = _run_chunked(
            cores_fn, carry, chunks, idxs, _design_scalars(cfg), bucket,
            sum(len(t) for t in traces))
    return [(_result(traces[b], cfg, t_end[b], skips[b], stall[b]),
             float(lg[b])) for b in range(n)]


def _pack_lane(segs: Sequence[CompiledTrace]
               ) -> tuple[CompiledTrace, np.ndarray, list[int]]:
    """Concatenate segment traces with OP_END markers after each.

    Returns the packed trace, the per-instruction *segment-local* index
    array (issue times restart per segment), and the marker positions at
    which the lane's per-segment results are emitted.
    """
    fields: dict[str, list[np.ndarray]] = {k: [] for k in
                                           ("opcode", "r_dst", "r_a", "r_b",
                                            "nbytes", "tm", "macs",
                                            "reusable")}
    idx_parts: list[np.ndarray] = []
    ends: list[int] = []
    pos = 0
    for t in segs:
        for k in fields:
            fields[k].append(getattr(t, k))
        idx_parts.append(np.arange(len(t), dtype=np.float64))
        pos += len(t)
        ends.append(pos)
        pos += 1
        fields["opcode"].append(np.array([OP_END], dtype=np.int32))
        for k in ("r_dst", "r_a", "r_b"):
            fields[k].append(np.zeros(1, dtype=np.int32))
        for k in ("nbytes", "tm", "macs"):
            fields[k].append(np.zeros(1, dtype=np.float64))
        fields["reusable"].append(np.zeros(1, dtype=bool))
        idx_parts.append(np.zeros(1, dtype=np.float64))
    cat = {k: np.concatenate(v) for k, v in fields.items()}
    packed = CompiledTrace(**cat, n_tl=sum(t.n_tl for t in segs),
                           n_ts=sum(t.n_ts for t in segs),
                           n_mm=sum(t.n_mm for t in segs),
                           useful_macs=sum(t.useful_macs for t in segs))
    return packed, np.concatenate(idx_parts), ends


def sweep_traces(traces: Sequence[CompiledTrace],
                 cfgs: Sequence[EngineConfig],
                 params: StreamModelParams | None = None,
                 backend: str = "fast") -> list[list[TimingResult]]:
    """Simulate the full (trace x config) grid: ``out[i][j]`` is trace *i*
    under config *j*.

    The jax path packs all traces back to back into *one* shared stream
    (OP_END markers emit each segment's results and reset the lane state),
    vmapped over the design configs only.  Sharing the instruction arrays
    across lanes keeps every per-step op a scalar-indexed slice -- the
    highest-throughput layout for multi-GEMM design sweeps.
    """
    if not traces or not cfgs:
        return [[] for _ in traces]
    total = sum(len(t) for t in traces) * len(cfgs)
    concrete = resolve_backend(backend, total)
    if concrete == "numpy":
        return [[_run_numpy_params(
                    t, cfg, params or StreamModelParams.for_config(cfg))[0]
                 for cfg in cfgs] for t in traces]

    base = params or StreamModelParams(load_ports=1)
    if base.is_port_model and base.store_ports is None:
        return [_sweep_port_mm(t, cfgs, params) for t in traces]
    sweep_fn = _jax_fns(base.is_port_model, emit_ends=True)[0]
    packed, idx, ends = _pack_lane(traces)
    chunks, idxs = _chunk_single(packed, idx)
    pick = [np.array([p - k * CHUNK for p in ends
                      if k * CHUNK <= p < (k + 1) * CHUNK], dtype=np.int64)
            for k in range(len(chunks))]
    # segment s of the packed stream is traces[s]; its result sits at the
    # s-th kept emission (picks are chunk-ordered = position-ordered)
    n = len(cfgs)
    cfgs_p = list(cfgs) + [cfgs[-1]] * (_pow2(n, lo=1) - n)
    inv_load = np.array(
        [1.0 / (params.load_ports if params is not None else c.load_ports)
         for c in cfgs_p], dtype=np.float64)
    bucket = _bucket_arrays(base, inv_load, np.float64(base.tail_share))
    with x64():
        carry = _init_carry(len(cfgs_p), base.burst_bytes)
        _, ys = _run_chunked(sweep_fn, carry, chunks, idxs,
                             _design_arrays(cfgs_p), bucket,
                             sum(len(t) for t in traces) * n, pick=pick)
    t_end, skips, stall, _ = ys
    return [[_result(traces[s], cfgs[j], t_end[j][s], skips[j][s],
                     stall[j][s]) for j in range(n)]
            for s in range(len(traces))]
