"""CLI drivers: serve.py / train.py / dryrun.py entry points."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).parent.parent


def _run(args, timeout=900, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-m", *args], env=env, capture_output=True,
        text=True, timeout=timeout, cwd=ROOT,
    )


def test_serve_cli_sim():
    out = _run(["repro.launch.serve", "--dataset", "sharegpt", "--rate", "2",
                "--n", "12", "--json"])
    assert out.returncode == 0, out.stderr
    data = json.loads(out.stdout[out.stdout.index("{"):])
    assert data["n_finished"] == 12
    assert data["scaling_migration_bytes"] == 0


def test_serve_cli_baseline():
    out = _run(["repro.launch.serve", "--system", "pd-disagg",
                "--dataset", "sharegpt", "--rate", "2", "--n", "8", "--json"])
    assert out.returncode == 0, out.stderr


def test_train_cli_loss_decreases():
    out = _run(["repro.launch.train", "--arch", "lwm-7b", "--steps", "6",
                "--batch", "2", "--seq", "64"])
    assert out.returncode == 0, out.stdout + out.stderr  # rc!=0 => loss rose


def test_train_cli_grad_compression():
    out = _run(["repro.launch.train", "--arch", "lwm-7b", "--steps", "4",
                "--batch", "2", "--seq", "48", "--grad-compression", "int8"])
    assert out.returncode == 0, out.stdout + out.stderr


def test_compile_cache_honours_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache: JAX reads it
    itself and the helper sets no other directory."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch):
    """Without the variable the cache sits at a fixed, gitignored path
    inside the checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
