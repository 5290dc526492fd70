"""Plain reference for the design-sweep cells.

Independent of the package under test: it imports nothing from ``repro``
and takes only the benchmark's own design files and GEMM shapes. The
shapes come from the layer equations of each architecture's adapter
(``perfbench/arch/<model_type>.py``); what follows depends on no
architecture. Two pieces, each a straight transcription of the paper's
model:

``lower``
    Algorithm 1's register-aware lowering of one GEMM into ``rasa_tl`` /
    ``rasa_mm`` / ``rasa_ts`` over eight tile registers: a 2x2 C block,
    two A and two B registers, n-outer/m-inner order, edge tiles padded.
``simulate``
    The in-order sub-stage pipeline (WL, FF, FS, DR) with the WLBP dirty
    bit, the WLS shadow buffer, a single weight-insertion port and
    ``load_ports`` tile loads per engine cycle; stores retire for free.

``simulate`` runs in the number type it is given: Python ``float`` (IEEE
double, the precision the configuration states) or ``numpy.float32``,
the lower-precision control.
"""

from __future__ import annotations

import math

TILE_M, TILE_K, TILE_N = 16, 32, 16
NUM_TREGS = 8
#: Algorithm 1: a 2x2 block of C tiles stays resident while K streams; A
#: tiles cycle through two registers and B tiles through two.
MC, NC, A_REGS, B_REGS = 2, 2, 2, 2
C_BASE, A_BASE, B_BASE = 0, MC * NC, MC * NC + A_REGS

TL, TS, MM = 0, 1, 2


def lower(M: int, K: int, N: int) -> list[tuple[int, int, int, int]]:
    """Algorithm 1's instruction stream: ``(op, dst, src1, src2)`` rows.

    Every tile is padded to 16x32x16, so each ``rasa_mm`` feeds 16 rows.
    """
    mt, kt, nt = -(-M // TILE_M), -(-K // TILE_K), -(-N // TILE_N)
    out: list[tuple[int, int, int, int]] = []
    emit = out.append
    for n0 in range(0, nt, NC):
        ncur = min(NC, nt - n0)
        for m0 in range(0, mt, MC):
            mcur = min(MC, mt - m0)
            for ni in range(ncur):
                for mi in range(mcur):
                    emit((TL, C_BASE + ni * MC + mi, 0, 0))
            order = [(mi, ni) for ni in range(ncur) for mi in range(mcur)]
            for _ in range(kt):
                # every A tile of the k-step fits its registers: load up front
                for mi in range(mcur):
                    emit((TL, A_BASE + mi % A_REGS, 0, 0))
                last_b = None
                for mi, ni in order:
                    b_reg = B_BASE + ni % B_REGS
                    if mi == order[0][0] and last_b != ni:
                        emit((TL, b_reg, 0, 0))
                        last_b = ni
                    emit((MM, C_BASE + ni * MC + mi, A_BASE + mi % A_REGS,
                          b_reg))
            for ni in range(ncur):
                for mi in range(mcur):
                    emit((TS, 0, C_BASE + ni * MC + mi, 0))
    return out


def simulate(stream, design: dict, num=float,
             whole_cycle_issue: bool = False) -> dict:
    """Cycles, instruction counts and WLBP skips of one stream on one design.

    ``num`` is the number type every time is held in. ``whole_cycle_issue``
    rounds each instruction's issue time up to a whole engine cycle, one
    step coarser than the issue slot the recurrence resolves. Utilization
    is taken on the host in double precision from the cycles, as the
    program takes it.
    """
    rows, cols = design["rows"], design["cols"]
    wlbp, wls, pipe = design["wlbp"], design["wls"], design["pipe"]
    wl = num(rows)
    fs = num(rows - 1)
    dr = num(cols + (1 if design["macs_per_pe"] == 2 else 0))
    ff = num(TILE_M)
    one = num(1)
    zero = num(0)
    issue = num(design["core_issue_width"]) * (
        num(design["core_clock_hz"]) / num(design["engine_clock_hz"]))
    load_lat = num(design["load_latency"])
    port_step = one / num(design["load_ports"])

    reg_ready = [zero] * NUM_TREGS
    gen = [0] * NUM_TREGS
    latched_reg, latched_gen = -1, -1
    next_free = zero
    p_ff_start = p_ff_end = p_fs_end = p_dr_end = zero
    have_prev = False
    wl_port_free = zero
    t_end = zero
    n_mm = n_tl = n_ts = skips = 0

    for idx, (op, dst, a, b) in enumerate(stream):
        t_issue = num(idx) / issue
        if whole_cycle_issue:
            t_issue = num(math.ceil(t_issue))
        if op == TL:
            n_tl += 1
            start = max(t_issue, next_free)
            next_free = start + port_step
            done = start + load_lat
            gen[dst] += 1
            reg_ready[dst] = done
            t_end = max(t_end, done)
            continue
        if op == TS:
            n_ts += 1
            t_end = max(t_end, max(t_issue, reg_ready[a]) + one)
            continue
        n_mm += 1
        t_ready_ac = max(t_issue, reg_ready[a], reg_ready[dst])
        t_ready_b = max(t_issue, reg_ready[b])
        reuse = wlbp and latched_reg == b and gen[b] == latched_gen
        if reuse:
            skips += 1
            ff_start = max(t_ready_ac, p_ff_end if have_prev else zero)
        elif wls:
            wl_start = max(t_ready_b, p_ff_start if have_prev else zero,
                           wl_port_free)
            hidden = have_prev and wl_start <= p_fs_end
            ready = wl_start + one if hidden else wl_start + wl
            ff_start = max(t_ready_ac, p_ff_end if have_prev else zero, ready)
        elif pipe:
            wl_start = max(t_ready_b, p_fs_end if have_prev else zero,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl,
                           p_dr_end if have_prev else zero)
        else:
            wl_start = max(t_ready_b, p_dr_end if have_prev else zero,
                           wl_port_free)
            ff_start = max(t_ready_ac, wl_start + wl)
        ff_end = ff_start + ff
        fs_end = ff_end + fs
        dr_end = fs_end + dr
        gen[dst] += 1
        reg_ready[dst] = dr_end
        latched_reg, latched_gen = b, gen[b]
        t_end = max(t_end, dr_end)
        if not reuse:
            wl_port_free = wl_start + wl
        p_ff_start, p_ff_end, p_fs_end, p_dr_end = (ff_start, ff_end,
                                                    fs_end, dr_end)
        have_prev = True

    cycles = float(t_end)
    peak = design["rows"] * design["cols"] * design["macs_per_pe"]
    useful = float(n_mm * TILE_M * TILE_K * TILE_N)
    return {"cycles": cycles, "n_mm": n_mm, "n_tl": n_tl, "n_ts": n_ts,
            "wl_skips": skips,
            "utilization": useful / (cycles * peak) if cycles > 0 else 0.0}
