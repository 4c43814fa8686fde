"""chip_smoke.py off the chip: it refuses without a TPU, its ``--dry-cpu``
rehearsal runs every stage, and the kernel it will run compiles for the chip
it will meet (AOT, for a described v5e topology — no chip attached).

The run on silicon itself is ``python chip_smoke.py`` through the chip tool
(``.claude/skills/verify/SKILL.md``).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _cpu_env(tmp_path) -> dict:
    """One CPU device (conftest's eight-device flag stays out: the one-chip
    path is what the rehearsal covers) and a cache of its own."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


def test_refuses_without_a_tpu(tmp_path):
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, SMOKE], cwd=REPO,
                          env=_cpu_env(tmp_path), capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert time.perf_counter() - t0 < 30
    assert proc.stdout == ""                      # no result of any kind
    reason = proc.stderr.strip().splitlines()
    assert len(reason) == 1 and "needs a TPU" in reason[0], proc.stderr


def test_no_entry_point_starts_a_process():
    """One process per chip: the smoke may not spawn, probe in a child or
    re-exec — a parent that touched JAX holds the chip."""
    with open(SMOKE) as f:
        src = f.read()
    for token in ("subprocess", "execv", "Popen", "os.system",
                  "multiprocessing", "jax_platforms"):
        assert token not in src, token


def test_dry_cpu_runs_every_stage(tmp_path):
    proc = subprocess.run([sys.executable, SMOKE, "--dry-cpu"], cwd=REPO,
                          env=_cpu_env(tmp_path), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # last: the verdict the driver reads, exactly these keys
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    out = json.loads(lines[-2])                   # before it: the summary
    assert out["ok"] is True and out["dry_run"] is True
    assert out["claim"] is None
    assert out["device"] == device
    assert set(out["cold_wall_s"]) == {"kernel", "train", "serve", "glm",
                                       "deeplearning"}
    kernel_only = {"pallas": 7, "fused_scatter": 0, "scatter": 0}
    assert out["train"]["gbm_hist_paths"] == kernel_only
    assert out["train"]["xgboost_hist_paths"] == kernel_only
    assert out["compile_cache"]["dir"] == str(tmp_path / "jax_cache")


def test_hist_kernel_compiles_for_v5e(monkeypatch):
    """AOT: libtpu compiles for a described topology with no chip attached.
    Both ends of the bin-storage envelope must lower to a Mosaic call, at a
    full node block, at the benchmark's own shapes (its cells' rows, storage
    and deepest call, and the one-feature call of their last level's totals)
    and at a frame wider than one feature block: a tile that overflows VMEM
    there fails here, on the CPU."""
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from h2o3_tpu.ops import pallas_hist
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert pallas_hist._INTERPRET is False
    on_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    for rows, feats, n_bins_tot, dtype, n_nodes in (
            (1_000_000, 28, 65, jnp.int8, 64),
            (1_000_000, 28, 257, jnp.int16, 64),
            (22_000_000, 28, 65, jnp.int8, 16),
            (11_000_000, 28, 257, jnp.int16, 16),
            (1_000_000, 500, 257, jnp.int16, 64),
            # the last level's totals: one feature whose bin is the node id
            (22_000_000, 1, 64, jnp.int32, 1),
            (20_000_000, 1, 1024, jnp.int32, 1)):
        exe = pallas_hist.hist_pallas.lower(
            spec((feats, rows), dtype), spec((rows,), jnp.int32),
            spec((rows,), jnp.float32), spec((rows,), jnp.float32),
            spec((rows,), jnp.float32),
            n_nodes=n_nodes, n_bins_tot=n_bins_tot).compile()
        assert "tpu_custom_call" in exe.as_text(), (rows, feats, n_bins_tot)


@pytest.mark.parametrize("rows", [2_000_000, 22_000_000])
def test_binomial_pass_compiles_for_v5e_as_a_matrix_product(monkeypatch, rows):
    """The binomial metrics' pass at the benchmark's row counts, compiled for
    the v5e with no chip attached: the score histogram is one MXU product a
    block (no scatter-add), in plain XLA (no Mosaic call: a GLM process
    never loads Pallas), and only a block's one-hot exists (the whole one at
    22M rows would be 17.6 GB)."""
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from h2o3_tpu.models.metrics import _binomial_pass
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    on_chip = SingleDeviceSharding(topo.devices[0])

    def spec(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=on_chip)

    exe = _binomial_pass.lower(spec(jnp.float32), spec(jnp.float32),
                               spec(jnp.bool_)).compile()
    text = exe.as_text()
    assert " scatter(" not in text and "tpu_custom_call" not in text
    assert " convolution(" in text
    assert exe.memory_analysis().temp_size_in_bytes < 1.5e9


#: what the cell's eight columns can hold (``GBM._bins_used``)
_CELL_USED = (12, 31, 7, 22, 300, 300, 100, 100)


@pytest.mark.parametrize("n_nodes, contraction, blocks, rows, bins_used", [
    (16, "packed", 1, 10_000_000, None), (32, "passes", 1, 10_000_000, None),
    (64, "passes", 1, 10_000_000, None), (256, "passes", 4, 10_000_000, None),
    (16, "packed", 1, 20_000_000, _CELL_USED),
    (64, "passes", 1, 20_000_000, _CELL_USED)])
def test_hist_kernel_compiles_at_the_categorical_cells_shapes(
        monkeypatch, n_nodes, contraction, blocks, rows, bins_used):
    """``gbm100-airline-cat-build``: 8 features x 301 bins (int16), up to 256
    parent slots: digits packed up to 21 slots, a pass a digit past that,
    four node blocks at the deepest level; and, at the cell's 20M rows, the
    call told what each column can hold: one-hot pieces of 16 to 304 rows
    stacked at 8-row offsets, longer row tiles. Compiled for the v5e with no
    chip attached."""
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from h2o3_tpu.ops import pallas_hist
    from h2o3_tpu.utils.telemetry import HIST_KERNEL_LEVELS
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    on_chip = SingleDeviceSharding(topo.devices[0])
    feats, n_bins_tot = 8, 301
    Nb, Fb, T = pallas_hist._plan(n_nodes, feats, n_bins_tot, bins_used)
    assert (Fb, -(-n_nodes // Nb)) == (feats, blocks)
    if bins_used is not None:
        assert T > pallas_hist._plan(n_nodes, feats, n_bins_tot)[2]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    counted = HIST_KERNEL_LEVELS.labels(contraction=contraction)
    before = counted.value
    exe = pallas_hist.hist_pallas.lower(
        spec((feats, rows), jnp.int16), spec((rows,), jnp.int32),
        spec((rows,), jnp.float32), spec((rows,), jnp.float32),
        spec((rows,), jnp.float32),
        n_nodes=n_nodes, n_bins_tot=n_bins_tot, bins_used=bins_used).compile()
    assert "tpu_custom_call" in exe.as_text()
    assert counted.value == before + 1


def test_kernel_refuses_an_operand_on_several_devices(monkeypatch):
    """The kernel holds no collective: ``hist_mesh`` tells one device from
    several, and only the former may reach ``hist_pallas``."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from h2o3_tpu.models import tree
    from h2o3_tpu.ops import pallas_hist
    monkeypatch.setattr(pallas_hist, "_INTERPRET", True)   # as if on a TPU
    assert pallas_hist.pallas_available(4, 28, 65)
    assert not pallas_hist.pallas_available(4, 28, 65, one_device=False)
    x = jnp.zeros((64, 4), jnp.int8)
    assert tree.hist_mesh(x) is None                       # one device
    odd = Mesh(np.array(jax.devices()), ("other",))
    spread = jax.device_put(x, NamedSharding(odd, P("other", None)))
    assert tree.hist_mesh(spread) is tree.UNFUSED          # no rows axis
