"""chip_smoke.py's CPU rehearsal, and the compile cache it relies on."""

import json
import os
import subprocess
import sys

import pytest

from ray_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_from_outside_stands(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.ensure_compile_cache() == "/somewhere/else"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"


def test_compile_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.ensure_compile_cache() == want
    # Set once, for this process and the workers that inherit its environment.
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_a_cached_program_is_found_by_its_metadata_too(tmp_path):
    """Two programs of the same instructions under different stage names are
    two entries of the cache once ``ensure_compile_cache`` has run: the
    second misses, and runs with its own names. In a process of its own,
    because jax reads the environment once."""
    script = """
import os, sys
from ray_tpu.util.compile_cache import CacheCounter, ensure_compile_cache
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
ensure_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from ray_tpu.models.common import stage
counter = CacheCounter()
def program(name):
    def f(x):
        with stage(name):
            return jnp.tanh(x @ x)
    return f
x = jnp.ones((64, 64))
for name in ("mlp", "mlp", "experts"):
    hits, misses = counter.hits, counter.misses
    text = jax.jit(program(name)).lower(x).compile().as_text()
    assert f"st.{name}" in text, name
    print(name, counter.hits - hits, counter.misses - misses)
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    out = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["mlp", "0", "1", "mlp", "1", "0", "experts", "0", "1"]


@pytest.mark.timeout(600)
def test_chip_smoke_cpu_rehearsal():
    """Both phases through JaxTrainer and Serve at tiny sizes, TPU leases
    handed out on one fake chip; the result says cpu."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--cpu-rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    evidence, result = map(json.loads, out.stdout.strip().splitlines())
    assert result == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    (train,) = evidence["train"]
    assert train["final_step"] == len(train["losses"]) >= 4
    assert train["visible_chips"] == "0"
    (replica,) = evidence["serve"]["replicas"]
    assert replica["visible_chips"] == "0"
    assert replica["stats"]["tokens_generated"] > 0
    assert len(evidence["serve"]["prefill_buckets"]) >= 2


def test_chip_smoke_fails_without_a_chip():
    """No TPU on this host: non-zero, the platform jax found is named, and
    no result is printed."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=170,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU chip" in out.stderr and "jax finds: cpu" in out.stderr
