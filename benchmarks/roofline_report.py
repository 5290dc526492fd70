"""Roofline table from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads benchmarks/results/dryrun/*.json (produced by
``python -m repro.launch.dryrun --all``) and prints the three-term roofline
per (arch x shape) on the single-pod mesh.
"""

from __future__ import annotations

import common  # noqa: F401  -- puts <repo>/src on sys.path

from pathlib import Path

from repro.roofline import (DRYRUN_DEVICE_KIND, analyze_all, format_report,
                            peaks_for)

from common import emit  # type: ignore

DRYRUN = Path(__file__).resolve().parent / "results" / "dryrun"


def main() -> None:
    cells = analyze_all(DRYRUN, peaks_for(DRYRUN_DEVICE_KIND))
    if not cells:
        print("# no dry-run artifacts; run: "
              "PYTHONPATH=src python -m repro.launch.dryrun --all")
        return
    for c in cells:
        emit(f"roofline_{c.arch}_{c.shape}", c.step_time_s * 1e6,
             f"bound={c.dominant};mfu={c.mfu:.3f};"
             f"mem_gib={c.peak_mem_bytes/2**30:.2f}")
    print()
    print(format_report(cells))


if __name__ == "__main__":
    main()
