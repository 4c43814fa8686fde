"""Where the persistent compile cache lives is decided outside the code:
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself — ``enable()``
must not override it), else ``<checkout>/.jax_cache``, the same path in every
process. ``H2O3TPU_COMPILE_CACHE`` only switches it on or off.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from h2o3_tpu.utils import compile_cache
on = compile_cache.enable(default_on=True)
print(json.dumps({"on": on, "updates": updates,
                  "config": jax.config.jax_compilation_cache_dir,
                  "stats_dir": compile_cache.stats()["dir"]}))
"""


def _probe(**env_over) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "H2O3TPU_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_placed_cache_is_never_set_in_code(tmp_path):
    placed = str(tmp_path / "placed")
    out = _probe(JAX_COMPILATION_CACHE_DIR=placed)
    assert out["on"] is True
    assert out["config"] == placed and out["stats_dir"] == placed
    assert "jax_compilation_cache_dir" not in out["updates"]


def test_default_dir_is_the_checkout_and_stable_across_processes():
    a, b = _probe(), _probe()
    want = os.path.join(REPO, ".jax_cache")
    assert a["config"] == b["config"] == want
    assert a["stats_dir"] == b["stats_dir"] == want


def test_switch_takes_on_off_and_rejects_a_path(monkeypatch):
    from h2o3_tpu.utils import compile_cache
    monkeypatch.setenv("H2O3TPU_COMPILE_CACHE", "0")
    assert compile_cache.enable(default_on=True) is False
    monkeypatch.setenv("H2O3TPU_COMPILE_CACHE", "/tmp/some/cache")
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        compile_cache.enable()
    monkeypatch.delenv("H2O3TPU_COMPILE_CACHE")
    assert compile_cache.enable() is False          # opt-in by default
