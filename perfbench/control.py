#!/usr/bin/env python3
"""The lower-precision control of ``correct``: must come out not correct.

    python3 perfbench/control.py --workload <cell> --questions <n> --seeds <s> [<s> ...] [--kind <kind>]

The plain reference, one step less exact, is put in the program's place:
it answers the first ``n`` questions of each seed's window list, and
``check.py`` holds those answers against the exact reference as a run
holds the program's. Answers are computed only for the pairs the check
reads. The kind is the mix's ``control`` unless ``--kind`` names one:

``float32``
    Every time of the recurrence held in float32, the precision below
    the float64 the simulator states; utilization is taken from the
    cycles in float64, as the program takes it.
``whole_cycle_issue``
    Issue times rounded up to whole engine cycles, one step coarser than
    the issue slot (a sixteenth of a cycle for the Table designs) that
    the recurrence resolves. For mixes whose times stay below 2**20
    cycles, where float32 holds every value exactly.

Prints one JSON line per seed; exits 1 if any seed's control came out
correct. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import check, reference  # noqa: E402
from perfbench.questions import questions  # noqa: E402


KINDS = {
    "float32": lambda stream, d: reference.simulate(stream, d, np.float32),
    "whole_cycle_issue": lambda stream, d: reference.simulate(
        stream, d, whole_cycle_issue=True),
}


class _LazyRow:
    """One GEMM's answers by design name, computed when read."""

    def __init__(self, shape, designs, memo, streams, simulate):
        self._shape = shape
        self._designs = {d["name"]: d for d in designs}
        self._memo = memo
        self._streams = streams
        self._simulate = simulate

    def __contains__(self, name):
        return name in self._designs

    def __len__(self):
        return len(self._designs)

    def get(self, name):
        d = self._designs.get(name)
        if d is None:
            return None
        key = (self._shape, tuple(d[t] for t in check.TIMING_KEYS))
        if key not in self._memo:
            if self._shape not in self._streams:
                self._streams.clear()
                self._streams[self._shape] = reference.lower(*self._shape)
            self._memo[key] = self._simulate(self._streams[self._shape], d)
        return self._memo[key]


def control_answers(c: dict, seed: int, n_questions: int,
                    kind: str) -> list[dict]:
    """The first ``n_questions`` of the window's list, answered by the
    control ``kind``."""
    simulate = KINDS[kind]
    memo: dict = {}
    streams: dict = {}
    out = []
    qs = questions(c["mix"], c["table"], seed)
    for _ in range(n_questions):
        q = next(qs)
        gemms = c["arch"].layer_gemms(c["config"], q["batch"], q["seq"],
                                      q["phase"])
        out.append({**q, "gemms": gemms,
                    "results": [_LazyRow(g[1:], q["designs"], memo, streams,
                                         simulate) for g in gemms]})
    return out


def run_control(c: dict, seed: int, n_questions: int,
                kind: str | None = None) -> dict:
    kind = kind or c["mix"]["control"]
    checks = check.compare(control_answers(c, seed, n_questions, kind), c,
                           seed)
    return {"seed": seed, "kind": kind, "correct": check.passed(checks),
            "checks": checks}


def main(argv: list[str] | None = None) -> int:
    from perfbench.run import load_cell

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--questions", type=int, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--kind", choices=sorted(KINDS))
    args = p.parse_args(argv)
    c = load_cell(args.workload)
    any_correct = False
    for seed in args.seeds:
        res = run_control(c, seed, args.questions, args.kind)
        any_correct |= res["correct"]
        print(json.dumps(res), flush=True)
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
