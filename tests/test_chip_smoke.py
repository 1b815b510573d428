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
