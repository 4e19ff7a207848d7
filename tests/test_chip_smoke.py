"""``chip_smoke.py`` refuses to report without a TPU, its dry run passes
every phase and check on the CPU, and the compile-cache helper keeps the
cache where ``JAX_COMPILATION_CACHE_DIR`` says or at one fixed path."""

import os
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]


def _smoke(*args: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("REPRO_STATE_RESIDENCY", None)
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


def test_chip_smoke_without_a_tpu_fails_and_reports_nothing():
    proc = _smoke()
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_chip_smoke_dry_run_passes_every_phase_but_reports_nothing():
    proc = _smoke("--dry-run")
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert '"ok": true' not in proc.stdout
    for name in ("a", "b", "c", "d", "d8", "e1", "e2"):
        assert f"[smoke {name}]" in proc.stdout
    assert "dry run: every phase and check passed" in proc.stdout


def test_compile_cache_dir_honours_env_and_is_fixed(monkeypatch, tmp_path):
    from repro.launch import jax_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
        assert jax_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets nothing
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv(jax_cache.ENV_VAR)
        first = jax_cache.enable_compile_cache()
        assert jax_cache.enable_compile_cache() == first
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
