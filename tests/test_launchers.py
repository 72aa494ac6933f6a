"""Launcher CLIs: train.py end-to-end (incl. sharded subprocess) and serve.py."""
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(args, timeout=420, env_extra=None):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-m", *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_launcher_reduced():
    with tempfile.TemporaryDirectory() as d:
        proc = _run(["repro.launch.train", "--arch", "mamba2-130m", "--reduced",
                     "--steps", "12", "--ckpt-dir", d])
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "done @ step 11" in proc.stdout
        assert any(p.name.startswith("step_") for p in Path(d).iterdir())


@pytest.mark.slow
def test_train_launcher_sharded_subprocess():
    """4-device (2,2) mesh through the real sharding path."""
    with tempfile.TemporaryDirectory() as d:
        proc = _run(["repro.launch.train", "--arch", "tinyllama-1.1b", "--reduced",
                     "--steps", "6", "--batch", "8", "--seq", "32",
                     "--devices", "4", "--mesh", "data,model=2,2", "--ckpt-dir", d])
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "devices=4" in proc.stdout
        assert "done @ step 5" in proc.stdout


def test_serve_launcher():
    proc = _run(["repro.launch.serve", "--requests", "6", "--new-tokens", "2",
                 "--policy", "Grouped"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "mean utility" in proc.stdout
    assert "batch[" in proc.stdout


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the fixed .jax_cache/ in the checkout."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "from_env"))
    assert enable_compile_cache(tmp_path) == str(tmp_path / "from_env")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache(tmp_path) == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
