"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny size with the
Pallas kernels interpreted, and ``main()`` refusing a CPU-only platform
before any phase runs."""

import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache
from repro.workload.compile import CompileOptions

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

TINY = CompileOptions(dim_cap=128, max_layers=1)


def test_phase_serve_rehearsal():
    res = chip_smoke.phase_serve(n_requests=3, options=TINY)
    assert res["ok"], res
    assert res["jit_gate"] is None and res["equal_to_numpy"]
    assert res["arb_rounds"] > 1           # the 64 B/cycle budget binds
    assert res["compiles"] >= 1 and res["compile_s"] > 0


def test_phase_sweep_rehearsal():
    res = chip_smoke.phase_sweep(seq=32, options=TINY)
    assert res["ok"], res
    assert res["designs"] == 8 and res["gemms"] == 5


def test_phase_kernels_rehearsal():
    shapes = chip_smoke.layer_gemm_shapes(seq=32, options=TINY)
    res = chip_smoke.phase_kernels(shapes, interpret=True)
    assert res["ok"], res
    assert res["calls"] == 3 * len(shapes)
    assert res["tpu_custom_call"] == "interpret"


def test_phase_lm_rehearsal():
    res = chip_smoke.phase_lm(smoke=True, prompt_len=8, new_tokens=4)
    assert res["ok"], res
    assert res["max_rel_logit_diff"] <= chip_smoke.LM_REL


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""                   # no phase line, no result
    assert "needs a TPU" in out.err


def test_layer_shapes_are_published_widths():
    """The chip run's GEMMs are qwen3-1.7b's own widths, uncapped."""
    shapes = chip_smoke.layer_gemm_shapes()
    assert (128, 2048, 6144) in shapes and (128, 6144, 2048) in shapes


def test_compile_cache_location(tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and is left to jax; otherwise the
    cache sits at a fixed ``<root>/.jax_cache``."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "env"))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.use_compile_cache(tmp_path) == \
            str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.use_compile_cache(tmp_path)
        assert path == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_smoke_path_never_imports_dryrun():
    """``repro.launch.dryrun`` forces 512 host devices through XLA_FLAGS at
    import; nothing the smoke imports may pull it in."""
    code = ("import sys, chip_smoke\n"
            "import repro.configs, repro.distributed.sharding, "
            "repro.launch.mesh, repro.models, repro.serving, "
            "repro.launch.compile_cache\n"
            "print('repro.launch.dryrun' in sys.modules)")
    root = pathlib.Path(chip_smoke.__file__).parent
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
