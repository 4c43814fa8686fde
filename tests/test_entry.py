"""Entry points against a sick or absent TPU backend: ``dryrun_multichip``
(a CPU-only virtual-mesh audit by construction) must complete without ever
touching the default backend.

Reference analog: the N-JVM localhost cloud always forms regardless of
cluster state (``scripts/multiNodeUtils.sh:21-26``).
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sick_env(n_cpu_flag: str | None = None) -> dict:
    """A driver-like env where initializing the default JAX backend FAILS:
    JAX_PLATFORMS names a platform that does not exist, so any parent-side
    ``jax.devices()`` raises immediately (simulating the round-3 wedged TPU
    without needing TPU hardware to be sick on cue)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "sick_tpu_simulated"
    if n_cpu_flag:
        env["XLA_FLAGS"] = n_cpu_flag
    return env


def test_env_probe_never_inits_backend():
    import __graft_entry__ as g

    saved = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        assert g._env_proves_cpu_devices(8)
        assert g._env_proves_cpu_devices(4)
        assert not g._env_proves_cpu_devices(16)
        os.environ["JAX_PLATFORMS"] = "tpu"
        assert not g._env_proves_cpu_devices(1)
        os.environ["JAX_PLATFORMS"] = "cpu"
        del os.environ["XLA_FLAGS"]
        assert not g._env_proves_cpu_devices(2)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_dryrun_completes_with_sick_backend():
    """dryrun_multichip must complete on the CPU-subprocess path in < 90 s
    even when the default backend is broken — the parent never initializes
    JAX, so the poisoned JAX_PLATFORMS is never even seen by a backend."""
    code = "import __graft_entry__ as g; g.dryrun_multichip(4)"
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_sick_env(),
        capture_output=True, text=True, timeout=180)
    dt = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "weak_scaling" in proc.stdout
    assert dt < 90, f"dryrun took {dt:.0f}s with a sick backend"

