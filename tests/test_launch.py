"""Launch-layer contracts that keep the chip's work visible: the compile
cache's location, the attention-implementation choice, the chip-peak
table, and the serving launcher's CPU path."""
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.mesh import CHIP_PEAKS, TARGET_KIND, chip_peaks
from repro.launch.serve import (FULL_DEFAULTS, SMOKE_DEFAULTS, cpu_platform,
                                parse_args)

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_env_var_wins(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/where/else")
    assert compile_cache.cache_dir() == "/some/where/else"


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = Path(compile_cache.cache_dir())
    assert path == REPO / ".jax_cache"
    # the same path every time, and never under the temp directory: a
    # cache that moves between runs is never hit
    assert compile_cache.cache_dir() == str(path)
    assert not str(path).startswith(tempfile.gettempdir())
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_enable_compile_cache_sets_jax_config(monkeypatch, tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# attention implementation
# ---------------------------------------------------------------------------

def test_resolve_attn_impl_explicit_pallas_on_mesh_raises():
    with pytest.raises(ValueError, match="4-device mesh"):
        ops.resolve_attn_impl("pallas", n_devices=4)


@pytest.mark.parametrize("n_devices", [1, 2])
def test_resolve_attn_impl_auto_and_explicit(n_devices):
    # auto picks pallas on one TPU device only; explicit "xla" stands
    on_one_tpu = n_devices == 1 and not ops.interpret_mode()
    assert ops.resolve_attn_impl(None, n_devices) == (
        "pallas" if on_one_tpu else "xla")
    assert ops.resolve_attn_impl("xla", n_devices) == "xla"
    with pytest.raises(ValueError, match="unknown attn_impl"):
        ops.resolve_attn_impl("flash", n_devices)


def test_interpret_mode_follows_backend():
    import jax
    assert ops.interpret_mode() == (jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# chip peaks
# ---------------------------------------------------------------------------

def test_chip_peaks_unknown_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")


def test_chip_peaks_v5e():
    peaks = chip_peaks(TARGET_KIND)
    assert peaks is CHIP_PEAKS["TPU v5 lite"]
    assert peaks["flops_bf16"] == 197e12 and peaks["hbm_bw"] == 819e9


# ---------------------------------------------------------------------------
# serving launcher
# ---------------------------------------------------------------------------

def test_serve_args_full_unless_smoke():
    full = parse_args(["--arch", "qwen3_0_6b"])
    assert not full.smoke
    assert {k: getattr(full, k) for k in FULL_DEFAULTS} == FULL_DEFAULTS
    smoke = parse_args(["--smoke", "--max-batch", "2"])
    assert smoke.smoke and smoke.max_batch == 2
    assert smoke.max_seq == SMOKE_DEFAULTS["max_seq"]


def test_cpu_platform_reads_jax_platforms(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cpu_platform()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert not cpu_platform()


@pytest.mark.slow
def test_serve_smoke_runs_on_cpu(tmp_path):
    """``python -m repro.launch.serve --smoke`` serves the float32 smoke
    config on the CPU end to end (own process: the launcher configures
    jax before importing it)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env[compile_cache.ENV_VAR] = str(tmp_path)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke", "--arch",
         "qwen3_0_6b", "--requests", "3", "--rounds", "2", "--max-new", "4",
         "--aot"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert f"compile cache: {tmp_path}" in out.stdout
    assert "qwen3-smoke: 2 layers, d_model 128, float32" in out.stdout
    assert "3 requests x 2 rounds" in out.stdout
    # 4 new tokens each: the first comes from prefill, 3 from decode
    assert "decode 18 tok" in out.stdout
    assert "mid-serve recompiles: 0" in out.stdout
