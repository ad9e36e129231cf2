"""Platform plumbing of the entry points: the chip peak table, the
persistent compilation cache, and the serve CLI's ``argv`` entry."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.engine.platform import kernel_backend
from repro.launch import compile_cache
from repro.launch.roofline import PEAKS, V5E_KIND, chip_peaks, roofline_terms

ROOT = Path(__file__).resolve().parents[1]


def test_v5e_peaks_are_the_published_ones():
    pk = chip_peaks(V5E_KIND)
    assert (pk.bf16_flops, pk.hbm_bw, pk.ici_link_bw) == (197e12, 819e9, 50e9)
    assert "TPU v5e" in pk.source


def test_every_roofline_reads_the_one_table():
    from benchmarks import common
    from repro import tune
    pk = PEAKS[V5E_KIND]
    assert (common.PEAK_FLOPS, common.HBM_BW) == (pk.bf16_flops, pk.hbm_bw)
    assert (tune.V5E.peak_flops, tune.V5E.hbm_bw) == (pk.bf16_flops,
                                                      pk.hbm_bw)
    t = roofline_terms(pk.bf16_flops, pk.hbm_bw, {})
    assert t["compute_s"] == 1.0 and t["memory_s"] == 1.0


@pytest.mark.parametrize("kind", ["TPU v9 ultra", "", "cpu"])
def test_unknown_device_kind_raises(kind):
    from repro.tune.cost_model import tpu_model
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks(kind)
    with pytest.raises(KeyError):
        tpu_model(kind)


def test_kernel_backend_is_interpret_off_tpu():
    assert jax.default_backend() != "tpu"
    assert kernel_backend() == "pallas_interpret"


def test_compile_cache_default_is_fixed_under_checkout():
    assert compile_cache.CACHE_DIR == str(ROOT / ".jax_cache")


def _run(code: str, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env)
    out = subprocess.run([sys.executable, "-c", code], env=full, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_package_import_sets_no_cache():
    got = _run("import jax, repro.launch.serve, repro.serving, "
               "repro.engine, repro.kernels.ops\n"
               "print(jax.config.jax_compilation_cache_dir)")
    assert got == ["None"]


def test_compile_cache_honours_env_and_lands_there(tmp_path):
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(2.0))\n")
    got = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert got == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir()), "no compiled entry in the cache dir"


def test_compile_cache_without_env_uses_checkout_dir():
    got = _run("from repro.launch.compile_cache import "
               "enable_compile_cache\n"
               "import jax\n"
               "print(enable_compile_cache())\n"
               "print(jax.config.jax_compilation_cache_dir)")
    assert got == [compile_cache.CACHE_DIR] * 2


def test_serve_main_takes_argv_and_returns_engine(monkeypatch):
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    eng, done = serve.main(["--arch", "gemma-2b", "--reduced",
                            "--requests", "3", "--slots", "2",
                            "--prompt-len", "8", "--max-new", "4",
                            "--max-len", "32", "--seed", "1"])
    assert eng.cfg.d_model == 128                # the reduced variant
    assert eng.dengine.resolved_backend == "reference"   # auto, off TPU
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(len(r.out_tokens) == 4 for r in done)
    assert all(0 <= t < eng.cfg.vocab for r in done for t in r.out_tokens)
