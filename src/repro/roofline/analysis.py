"""Three-term roofline from the dry-run artifacts (EXPERIMENTS.md §Roofline).

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

Sources: ``compiled.cost_analysis()`` (per-device, so the per-device values
are used directly with per-device peaks) and the optimized-HLO collective
parse from dryrun.py.  cost_analysis counts a scan body once (measured), so
*totals* are reconstructed from layer-unrolled reduced-depth compiles:

    total = embed_head + n_units x per_unit

where a "unit" is one scanned layer (transformers/ssm) or one group of
``attn_every`` layers + the shared block (hybrid).  The dry-run stores the
full-depth artifact (memory/sharding proof) and the reduced-depth artifacts
(flops/bytes/collectives); this module combines them.

Hardware constants come from :data:`PEAKS`, keyed by the device kind jax
reports; a device missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from ..config import SHAPES


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float                  # bf16 / chip
    hbm_bw: float                      # bytes/s / chip
    ici_bw: float                      # bytes/s / link
    hbm_bytes: float                   # HBM capacity / chip


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e
#: (reported as "TPU v5 lite"): Google Cloud documentation, "TPU v5e" --
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over
#: four links (~50 GB/s per link and direction).
PEAKS: dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                      hbm_bytes=16 * 2**30),
}

#: the device the dry-run compiles for (``repro.launch.dryrun``: a 16x16
#: v5e pod per pod)
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> HW:
    """The :data:`PEAKS` entry for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")


@dataclasses.dataclass
class CellRoofline:
    arch: str
    shape: str
    devices: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    model_flops: float                 # 6*N*D (dense) / 6*N_active*D (moe)
    peak_mem_bytes: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    extrapolated: bool = False
    hw: HW | None = None               # the peaks the terms were taken at

    def finalize(self, hw: HW) -> "CellRoofline":
        self.hw = hw
        self.compute_s = self.flops_per_device / hw.peak_flops
        self.memory_s = self.bytes_per_device / hw.hbm_bw
        self.collective_s = self.coll_bytes_per_device / hw.ici_bw
        return self

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs (remat/padding/masked-attention
        waste shows up here)."""
        total_hlo = self.flops_per_device * self.devices
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline step time."""
        denom = self.step_time_s * self.devices * self.hw.peak_flops
        return self.model_flops / denom if denom else 0.0


def model_flops_for(arch: str, shape: str) -> float:
    """6*N*D (N = active params, D = tokens processed).  For decode shapes
    D = batch (one token per sequence) but attention also reads the cache:
    +2*cache_token_kv_flops; we report the 6*N*D convention and note cache
    reads separately in §Roofline."""
    from ..configs import get_config
    cfg = get_config(arch)
    seq, batch, kind = SHAPES[shape]
    n_active = cfg.model.active_param_count()
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_active * tokens     # forward only
    return 2.0 * n_active * batch          # decode: one token/sequence


def load_cell(results_dir: Path, arch: str, shape: str,
              multi_pod: bool = False) -> dict | None:
    pod = "pod2" if multi_pod else "pod1"
    p = results_dir / f"{arch}__{shape}__{pod}.json"
    if not p.exists():
        return None
    return json.loads(p.read_text())


def _coll_sum(cell: dict) -> float:
    colls = cell.get("collectives_per_device_bytes", {})
    return sum(v for k, v in colls.items() if not k.endswith("_count"))


def analyze_cell(cell: dict, hw: HW,
                 d0: dict | None = None, du: dict | None = None) -> CellRoofline:
    """Roofline terms for one cell.  With the reduced-depth unrolled
    artifacts (d0 = embed+head only, du = one unit of layers), totals are

        total = d0 + n_units * (du - d0)

    which corrects cost_analysis's count-scan-body-once behaviour.  Without
    them, the raw (undercounted) scanned numbers are used and flagged."""
    flops = cell["cost_per_device"]["flops"]
    byts = cell["cost_per_device"]["bytes_accessed"]
    coll = _coll_sum(cell)
    extrapolated = False
    if d0 is not None and du is not None and not d0.get("skipped"):
        unit = cell.get("unit_layers", 1)
        n_units = cell.get("total_layers", unit) // unit
        def comb(a, b):
            return a + n_units * max(b - a, 0.0)
        flops = comb(d0["cost_per_device"]["flops"],
                     du["cost_per_device"]["flops"])
        byts = comb(d0["cost_per_device"]["bytes_accessed"],
                    du["cost_per_device"]["bytes_accessed"])
        coll = comb(_coll_sum(d0), _coll_sum(du))
        extrapolated = True
    r = CellRoofline(
        arch=cell["arch"], shape=cell["shape"], devices=cell["devices"],
        flops_per_device=flops,
        bytes_per_device=byts,
        coll_bytes_per_device=coll,
        model_flops=model_flops_for(cell["arch"], cell["shape"]),
        peak_mem_bytes=cell["memory"]["peak_bytes_per_device"],
        extrapolated=extrapolated,
    )
    return r.finalize(hw)


def _load_depth(results_dir: Path, arch: str, shape: str, depth: int) -> dict | None:
    p = results_dir / f"{arch}__{shape}__pod1__d{depth}.json"
    return json.loads(p.read_text()) if p.exists() else None


def analyze_all(results_dir: str | Path, hw: HW,
                multi_pod: bool = False) -> list[CellRoofline]:
    results_dir = Path(results_dir)
    from ..configs import all_cells, get_config
    out = []
    for arch, shape, ok, why in all_cells():
        cell = load_cell(results_dir, arch, shape, multi_pod)
        if cell is None or cell.get("skipped"):
            continue
        unit = cell.get("unit_layers", 1)
        d0 = _load_depth(results_dir, arch, shape, 0)
        du = _load_depth(results_dir, arch, shape, unit)
        out.append(analyze_cell(cell, hw, d0=d0, du=du))
    return out


def format_report(cells: list[CellRoofline]) -> str:
    hdr = (f"{'arch':24s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'coll_s':>10s} {'bound':>10s} {'mem_GiB':>8s} {'MFU%':>6s} "
           f"{'useful%':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for c in cells:
        lines.append(
            f"{c.arch:24s} {c.shape:12s} {c.compute_s:10.4f} "
            f"{c.memory_s:10.4f} {c.collective_s:10.4f} {c.dominant:>10s} "
            f"{c.peak_mem_bytes/2**30:8.2f} {100*c.mfu:6.1f} "
            f"{100*c.useful_flops_ratio:8.1f}")
    return "\n".join(lines)
