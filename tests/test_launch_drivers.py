"""Launch-driver smoke tests (subprocess CLIs: train.py / serve.py)."""

import os
import subprocess
import sys

import pytest


def _run_module(mod: str, *argv: str, timeout=420, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, "-m", mod, *argv],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"{mod} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout + proc.stderr


def test_train_driver(tmp_path):
    out = _run_module(
        "repro.launch.train",
        "--arch", "olmo-1b", "--reduced", "--steps", "6", "--batch", "2",
        "--seq", "32", "--ckpt-every", "3", "--ckpt-dir", str(tmp_path),
    )
    assert "done: step=6" in out
    assert any(p.startswith("step_") for p in os.listdir(tmp_path))


def test_serve_driver_restores_checkpoint(tmp_path):
    _run_module(
        "repro.launch.train",
        "--arch", "olmo-1b", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "32", "--ckpt-every", "2", "--ckpt-dir", str(tmp_path),
    )
    out = _run_module(
        "repro.launch.serve",
        "--arch", "olmo-1b", "--reduced", "--requests", "2",
        "--new-tokens", "4", "--ckpt-dir", str(tmp_path),
    )
    assert "restored step" in out
    assert "served 2 requests" in out


def test_serve_cp_driver(tmp_path):
    out = _run_module(
        "repro.launch.serve_cp",
        "--requests", "4", "--batch-size", "2", "--dim", "6",
        "--n-iters", "2", "--tuning-cache", str(tmp_path / "tuning.json"),
        # an entry point caches compiles in the checkout unless told otherwise
        env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")},
    )
    assert "served 4 problems" in out
    assert "signatures=2 compiles=2" in out


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_entry_points_cache_compiles_at_a_fixed_path(monkeypatch, tmp_path, env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the entry points pin the cache to
    <checkout>/.jax_cache (a cache is only found again at the same path);
    with it set, JAX reads it and nothing is set in code."""
    import jax

    from repro.launch import compile_cache

    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert str(compile_cache.CHECKOUT_CACHE) == os.path.join(checkout, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert compile_cache.use_checkout_cache() == str(compile_cache.CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == str(compile_cache.CHECKOUT_CACHE)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
            assert compile_cache.use_checkout_cache() == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()  # later compiles re-read the config
