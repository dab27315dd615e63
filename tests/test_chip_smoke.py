"""chip_smoke.py: it refuses to run without a TPU, and its phases — the
serve path against the plain decode loop, the megakernel drain against
its oracle — hold at a tiny size on the CPU."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import kernels
from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("extra", [[], ["--streams", "--chunked-prefill",
                                        "--prefill-chunk", "4"]],
                         ids=["plain", "streams"])
def test_serve_phase_tiny(extra):
    r = load_smoke().serve_phase(extra, reduced=True, requests=3, max_new=4,
                                 max_batch=2, max_seq=64)
    assert r["tokens"] == 12
    # float32 on the CPU: the engine's greedy tokens are the plain loop's
    assert r["matched"] == r["steps"] == 4 and r["max_gap"] == 0.0


def test_drain_phase_matches_oracle():
    r = load_smoke().drain_phase(seed=1)
    assert r["interpreted"] is True          # CPU: the Pallas interpreter
    assert r["heals"] == 0 and r["rows"] == r["items"] + 3
    assert r["err"] < 1e-5 and r["ws_err"] < 1e-5


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None)])
def test_default_interpret_only_on_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="neither"):
            kernels.default_interpret()
    else:
        assert kernels.default_interpret() is want


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    want = os.path.join(REPO, ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
