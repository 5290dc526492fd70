"""Roofline analysis from compiled dry-run artifacts."""

from .analysis import (DRYRUN_DEVICE_KIND, HW, PEAKS, CellRoofline,
                       analyze_all, analyze_cell, format_report, peaks_for)

__all__ = ["DRYRUN_DEVICE_KIND", "HW", "PEAKS", "CellRoofline",
           "analyze_cell", "analyze_all", "format_report", "peaks_for"]
