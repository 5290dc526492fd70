"""Roofline + dry-run plumbing tests (no compiles: synthetic artifacts)."""

import json

import pytest

from repro.launch.dryrun import parse_collectives
from repro.roofline.analysis import (DRYRUN_DEVICE_KIND, analyze_cell,
                                     model_flops_for, peaks_for)

V5E = peaks_for(DRYRUN_DEVICE_KIND)


def test_parse_collectives_sums_operand_bytes():
    hlo = """
  ENTRY main {
    %ag = f32[16,128] all-gather(%x), replica_groups={}
    %ar = bf16[1024] all-reduce(%y), to_apply=%add
    %rs = (f32[8,8], f32[8,8]) reduce-scatter(%a, %b), dimensions={0}
    %cp = f32[4,4] collective-permute(%z), source_target_pairs={{0,1}}
    %agd = f32[16,128] all-gather-done(%t)
  }
    """
    out = parse_collectives(hlo)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 1024 * 2
    assert out["reduce-scatter"] == 2 * 8 * 8 * 4
    assert out["collective-permute"] == 4 * 4 * 4
    assert out["all-gather_count"] == 1
    # -done ops must not be double counted
    assert out.get("all-gather", 0) == 16 * 128 * 4


def _cell(flops=1e12, byts=1e11, coll=1e9, devices=256, unit=1, total=10):
    return {
        "arch": "qwen3-1.7b", "shape": "train_4k", "devices": devices,
        "unit_layers": unit, "total_layers": total,
        "cost_per_device": {"flops": flops, "bytes_accessed": byts},
        "collectives_per_device_bytes": {"all-reduce": coll,
                                         "all-reduce_count": 4},
        "memory": {"peak_bytes_per_device": 8 * 2**30},
    }


def test_analyze_cell_terms():
    r = analyze_cell(_cell(flops=1e14), V5E)
    assert r.compute_s == pytest.approx(1e14 / V5E.peak_flops)
    assert r.memory_s == pytest.approx(1e11 / V5E.hbm_bw)
    assert r.collective_s == pytest.approx(1e9 / V5E.ici_bw)
    assert r.dominant == "compute"     # 0.51 s > 0.12 s > 0.02 s
    assert r.step_time_s == r.compute_s


def test_analyze_cell_depth_extrapolation():
    base = _cell()
    d0 = _cell(flops=2e10, byts=1e9, coll=1e8)
    du = _cell(flops=3e10, byts=2e9, coll=3e8)
    r = analyze_cell(base, V5E, d0=d0, du=du)
    assert r.extrapolated
    # total = d0 + 10 * (du - d0)
    assert r.flops_per_device == pytest.approx(2e10 + 10 * 1e10)
    assert r.coll_bytes_per_device == pytest.approx(1e8 + 10 * 2e8)


def test_dominant_collective():
    r = analyze_cell(_cell(flops=1e9, byts=1e9, coll=1e12), V5E)
    assert r.dominant == "collective"


def test_model_flops_conventions():
    t = model_flops_for("qwen3-1.7b", "train_4k")
    p = model_flops_for("qwen3-1.7b", "prefill_32k")
    d = model_flops_for("qwen3-1.7b", "decode_32k")
    # train: 6*N*tokens; prefill: 2*N*tokens; decode: 2*N*batch
    assert t / (4096 * 256) == pytest.approx(3 * p / (32768 * 32))
    assert d == pytest.approx(p / (32768 * 32) * 128)
    # moe uses ACTIVE params
    from repro.configs import get_config
    grok = model_flops_for("grok-1-314b", "train_4k")
    n_active = get_config("grok-1-314b").model.active_param_count()
    assert grok == pytest.approx(6.0 * n_active * 4096 * 256)


def test_unknown_device_kind_has_no_peaks():
    """Peaks are keyed by device kind; a device missing from the table is
    an error, never a silent v5e default."""
    assert V5E.peak_flops == 197e12 and V5E.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
