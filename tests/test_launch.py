"""Entry-point plumbing of ``repro.launch.train``."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.train import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_env_var_wins(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and nothing is set."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path  # fixed: no pid, time or tmpdir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_mesh_check_grads_runs_the_sharded_step_on_four_devices():
    """``--mesh 2x2 --check-grads`` on four (fake CPU) devices re-runs the
    step through shard_map, whose psum the gradient check then holds to
    the full-batch oracle: a shard's own gradient would fail it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--backend", "ntx",
         "--model", "qwen1_5_0_5b", "--reduced", "--steps", "1",
         "--batch", "4", "--seq", "8", "--mesh", "2x2", "--check-grads"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    assert "executing via shard_map data-parallel" in out
    assert "gradient check vs jax.grad: 16 params OK" in out
