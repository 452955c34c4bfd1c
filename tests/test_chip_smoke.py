"""chip_smoke.py's own contract, and the compile-cache helper it shares
with the other entry points. The smoke proper needs a TPU; what the suite
can pin is that it cannot go green without one."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from apex_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd=_REPO, **env):
    full = {k: v for k, v in os.environ.items()
            if k != compile_cache.CACHE_DIR_ENV}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=600)


def test_default_refuses_without_a_tpu():
    r = _run([_SMOKE])
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'platform': 'cpu'" in r.stderr
    assert r.stdout.strip() == "", "no result may be printed off a TPU"


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(_SMOKE, tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.slow       # ~45 s: both legs + the four-device phase, toy sizes
def test_rehearsal_runs_every_phase_and_is_not_a_pass():
    r = _run([_SMOKE, "--rehearse"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert {l.get("leg") or l.get("phase") for l in lines} >= {
        "resnet", "bert", "distributed"}


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_cache_dir_from_outside_is_left_alone(monkeypatch, tmp_path,
                                              restore_cache_config):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch,
                                                    restore_cache_config):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    here = compile_cache.enable_compile_cache()
    assert here == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == here
    other = _run(["-c", "from apex_tpu.utils import enable_compile_cache;"
                        "print(enable_compile_cache())"])
    assert other.stdout.strip().splitlines()[-1] == here
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
