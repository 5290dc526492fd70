"""The one question generator every traffic mix goes through.

A mix file (``perfbench/traffic/<name>.json``) holds only parameters:

``phase``
    ``prefill`` or ``decode``.
``batch``, ``seq``
    Menus of values. Each is drawn without repetition in a seeded order:
    every block of ``len(menu)`` questions holds each value once, and the
    next block is a fresh shuffle. Every seed therefore gets the same
    sizes, in another order.
``designs``
    ``{"kind": "table"}``: every design of ``perfbench/designs.json``.
    ``{"kind": "neighbours", ...}``: single-knob neighbours of one Table
    design (the bases are drawn like ``batch``), ``take`` of each knob's
    menu, drawn without repetition. The knobs are the array shape at a
    fixed multiplier count, the flag sets, ``load_latency`` and
    ``load_ports``, as a greedy engine search makes them.
``warmup``
    Overrides for the warm-up question, which comes from a seed stream of
    its own and never from the window's list.

A question is a plain dict: ``batch``, ``seq``, ``phase`` and ``designs``
(a list of design dicts, each with a unique ``name``).
"""

from __future__ import annotations

import random
from typing import Iterator

FLAG_KEYS = ("pipe", "wlbp", "wls", "double_buffer")


def _blocks(values: list, rng: random.Random) -> Iterator:
    while True:
        block = list(values)
        rng.shuffle(block)
        yield from block


def neighbours(base: dict, knobs: dict, rng: random.Random) -> list[dict]:
    """Single-knob neighbours of ``base``: ``knobs["take"][knob]`` of each."""
    menus: dict[str, list[tuple[str, dict]]] = {
        "shape": [], "flags": [], "load_latency": [], "load_ports": []}
    mult = knobs["multipliers"]
    lo, hi = knobs["cols_range"]
    for rows in knobs["rows"]:
        for macs in knobs["macs_per_pe"]:
            cols = mult // (rows * macs)
            if rows * macs * cols != mult or not lo <= cols <= hi:
                continue
            if (rows, macs) != (base["rows"], base["macs_per_pe"]):
                menus["shape"].append(
                    (f"{rows}x{cols}x{macs}",
                     {"rows": rows, "cols": cols, "macs_per_pe": macs}))
    for flags in knobs["flags"]:
        change = dict(zip(FLAG_KEYS, flags))
        if any(base[k] != v for k, v in change.items()):
            tag = "".join("1" if v else "0" for v in flags)
            menus["flags"].append((f"flags{tag}", change))
    for knob in ("load_latency", "load_ports"):
        for v in knobs[knob]:
            if v != base[knob]:
                menus[knob].append((f"{knob}{v}", {knob: v}))
    out = []
    for knob, n in knobs["take"].items():
        for tag, change in rng.sample(menus[knob], n):
            out.append({**base, **change, "name": f"{base['name']}~{tag}"})
    return out


def questions(mix: dict, table: list[dict], seed: int,
              stream: str = "window") -> Iterator[dict]:
    """The seeded, endless question list of one mix."""
    rng = random.Random(f"{stream}:{seed}")
    batches = _blocks(mix["batch"], rng)
    seqs = _blocks(mix["seq"], rng)
    spec = mix["designs"]
    bases = _blocks(table, rng)
    while True:
        q = {"batch": next(batches), "seq": next(seqs), "phase": mix["phase"]}
        if spec["kind"] == "table":
            q["designs"] = [dict(d) for d in table]
        else:
            q["designs"] = neighbours(next(bases), spec, rng)
        yield q


def warmup_question(mix: dict, table: list[dict], seed: int) -> dict:
    """The warm-up question: drawn outside the window's list."""
    q = next(questions(mix, table, seed, stream="warmup"))
    over = mix.get("warmup", {})
    return {**q, **{k: v for k, v in over.items() if k in ("batch", "seq")}}
