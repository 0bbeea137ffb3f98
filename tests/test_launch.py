"""Entry-point plumbing: the compile-cache switch and the serving
CLI's parameter build."""
import types

import jax.numpy as jnp

from repro.configs import get_config
from repro.launch import compile_cache, serve


def _recording_jax(monkeypatch):
    """Stand in for jax.config so the test process's cache stays off."""
    calls = {}
    fake = types.SimpleNamespace(config=types.SimpleNamespace(
        update=lambda k, v: calls.__setitem__(k, v)))
    monkeypatch.setattr(compile_cache, "jax", fake)
    return calls


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    calls = _recording_jax(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls      # JAX reads the env
    assert calls["jax_enable_compilation_cache"] is True
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    calls = _recording_jax(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    repo = compile_cache.DEFAULT_DIR.parent
    assert got == str(repo / ".jax_cache")
    assert (repo / "src" / "repro" / "launch").is_dir()
    assert calls["jax_compilation_cache_dir"] == got
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


def test_serve_load_builds_params_in_config_dtype(monkeypatch):
    """Published widths keep the config's dtype; smoke presets are f32."""
    model, params = serve.load("granite-moe-3b-a800m", smoke=True)
    assert model.cfg.dtype == "float32"
    assert params["embed"]["tok"].dtype == jnp.float32
    bf16 = get_config("granite-moe-3b-a800m", smoke=True)
    assert bf16.dtype == "bfloat16"
    monkeypatch.setattr(serve, "get_config", lambda arch, smoke: bf16)
    model, params = serve.load("granite-moe-3b-a800m")
    assert params["embed"]["tok"].dtype == jnp.bfloat16
    # norm scales keep their float32 override
    assert params["ln_f"]["w"].dtype == jnp.float32


def test_weights_from_a_seed_match_across_processes(tmp_path):
    """Parameters are keyed per path by crc32, not by str hash(), whose
    salt changes with every process: the same seed gives the same
    weights in any run."""
    import os
    import subprocess
    import sys
    code = ("import numpy as np; from repro.launch import serve; "
            "_, p = serve.load('granite-moe-3b-a800m', smoke=True); "
            "print(float(np.asarray(p['embed']['tok']).sum()))")
    sums = set()
    for salt in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": salt, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True)
        sums.add(out.stdout.strip().splitlines()[-1])
    assert len(sums) == 1, sums
