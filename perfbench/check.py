"""The comparison that decides ``correct``.

Every question the window answered is held against the plain reference,
computed in float64 on the host once the window has closed: the layer
equations of the configuration's adapter (``layer_gemms`` of
``perfbench/arch/<model_type>.py``), then the lowering and timing of
``perfbench/reference.py``:

``unanswered``
    (GEMM, design) pairs due from the reference's GEMM list for which the
    program returned no answer.
``shape_mismatch``
    GEMMs whose ``(M, K, N)`` differ from the layer equations, counted by
    position, plus any difference in the number of GEMMs.
``count_mismatch``
    Checked pairs whose ``rasa_mm``/``rasa_tl``/``rasa_ts`` counts or WL
    skips differ.
``cycles_rel_gap``, ``util_rel_gap``
    The widest relative gap of a checked pair's cycles and MAC
    utilization.

Every GEMM of every question is checked. The mix's
``check_designs_per_gemm`` ``k`` (absent: all designs) checks GEMM ``i``
under designs ``o + i*k`` to ``o + i*k + k - 1``, counted round the
question's design list from an offset ``o`` drawn from the seed: where
the GEMMs times ``k`` reach the number of designs, every design is
checked on some GEMM of every question. Identical (shape, design) pairs
are simulated once. All limits
are 0: the simulator's outputs are exact functions of the question, and
a change that only makes it faster leaves them bit-identical.
"""

from __future__ import annotations

import random

from . import reference

LIMITS = {"unanswered": 0, "shape_mismatch": 0, "count_mismatch": 0,
          "cycles_rel_gap": 0.0, "util_rel_gap": 0.0}
COUNTS = ("n_mm", "n_tl", "n_ts", "wl_skips")
TIMING_KEYS = ("rows", "cols", "macs_per_pe", "pipe", "wlbp", "wls",
               "load_latency", "load_ports", "core_issue_width",
               "core_clock_hz", "engine_clock_hz")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def compare(answered: list[dict], c: dict, seed: int) -> dict:
    """``{name: {"value": reading, "limit": limit}}`` over ``answered``,
    the questions of cell ``c`` (as ``run.load_cell`` gives it).

    Each answered question carries ``gemms`` (``(name, M, K, N)`` as the
    program built them) and ``results`` (per GEMM, ``{design name:
    {cycles, n_mm, n_tl, n_ts, wl_skips, utilization}}``).
    """
    rng = random.Random(f"check:{seed}")
    layer_gemms, cfg = c["arch"].layer_gemms, c["config"]
    k = c["mix"].get("check_designs_per_gemm")
    got = dict.fromkeys(LIMITS, 0)
    got["cycles_rel_gap"] = got["util_rel_gap"] = 0.0
    memo: dict = {}
    streams: dict = {}
    for q in answered:
        want = [g[1:] for g in layer_gemms(cfg, q["batch"], q["seq"],
                                           q["phase"])]
        have = [tuple(g[1:]) for g in q["gemms"]]
        got["shape_mismatch"] += abs(len(want) - len(have)) + sum(
            w != h for w, h in zip(want, have))
        results = q["results"] or []
        streams = {s: v for s, v in streams.items() if s in want}
        designs = q["designs"]
        n = len(designs)
        offset = rng.randrange(n)
        for i, shape in enumerate(want):
            row = results[i] if i < len(results) else {}
            got["unanswered"] += sum(d["name"] not in row for d in designs)
            pick = designs if k is None or k >= n else [
                designs[(offset + i * k + j) % n] for j in range(k)]
            for d in pick:
                ans = row.get(d["name"])
                if ans is None:
                    continue
                key = (shape, tuple(d[t] for t in TIMING_KEYS))
                if key not in memo:
                    if shape not in streams:
                        streams[shape] = reference.lower(*shape)
                    memo[key] = reference.simulate(streams[shape], d)
                ref = memo[key]
                got["count_mismatch"] += any(ans[c] != ref[c] for c in COUNTS)
                got["cycles_rel_gap"] = max(
                    got["cycles_rel_gap"],
                    _rel(float(ans["cycles"]), ref["cycles"]))
                got["util_rel_gap"] = max(
                    got["util_rel_gap"],
                    _rel(float(ans["utilization"]), ref["utilization"]))
    return {name: {"value": got[name], "limit": lim}
            for name, lim in LIMITS.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
