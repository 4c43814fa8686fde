"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the flagship (BASELINE.json's GBM on
HIGGS-shaped data):

    Frame.from_arrays → GBM.train (Job, DKV, metrics) → H2OServer →
    H2OClient.score over HTTP (``POST /3/Score``)

preceded by the histogram kernel against its reference on silicon, and
followed by one short GLM and one short DeepLearning build. Every stage is
fatal: an assertion or exception ends the run non-zero and no result is
printed. A passing run ends with two JSON lines. The summary: the device as
JAX reports it, each stage's cold wall time (compilation included — they are
not speeds), the histogram-path counts, the compile-cache state and peak
device memory; it states no speed: ``"claim": null``. Then, last, the verdict
the driver reads, with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py              # needs a TPU; exits non-zero without
    python chip_smoke.py --dry-cpu    # same stages, toy sizes, on CPU with
                                      # the kernel in interpret mode

There is no other path that runs off-chip: no platform override, no child
probe, no re-exec. A chip belongs to one process, so this starts none.
"""

from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import sys
import time

import numpy as np

#: full-width sizes; rows are the one thing a time limit may cut
#: (11M / 5.5M / 2.2M), never features, bins or depth
FULL = dict(kernel_rows=1_000_000, train_rows=11_000_000,
            glm_rows=1_000_000, dl_rows=60_000)
DRY = dict(kernel_rows=4_096, train_rows=4_096, glm_rows=4_096, dl_rows=512)

NFEAT, DEPTH = 28, 6
#: rows a request: the 8-, 128- and 4096-row buckets of serving/scorer.py
#: (10,000 rows is scored in max-bucket slices)
SCORE_SIZES = (1, 100, 10_000)
#: the hilo tolerance of tests/test_pallas_interpret.py
HIST_RTOL, HIST_ATOL = 5e-4, 5e-3
#: /3/Score against predict: the scorer is ONE fused program, predict runs
#: op by op, so ``f0 + lr·Σtrees`` may round once there and twice here
SCORE_ATOL = 2 * float(np.finfo(np.float32).eps)


def log(msg: str) -> None:
    print(f"# chip_smoke: {msg}", flush=True)


def timed(walls: dict, name: str, fn, *args):
    """Run one stage; book its wall seconds (cold: compilations included)."""
    t0 = time.perf_counter()
    out = fn(*args)
    walls[name] = round(time.perf_counter() - t0, 2)
    log(f"{name} passed in {walls[name]} s")
    return out


# -- the frames, each from a seed ----------------------------------------------

def _higgs_frame(rows: int):
    from h2o3_tpu.frame.frame import Frame
    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, NFEAT)).astype(np.float32)
    logit = X[:, :4] @ np.array([1.2, -0.8, 0.5, 0.3], np.float32) \
        + 0.2 * X[:, 4] * X[:, 5]
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    cols = {f"x{i}": X[:, i] for i in range(NFEAT)}
    cols["y"] = np.where(y == 1, "s", "b")
    return Frame.from_arrays(cols)


def _glm_frame(n: int):
    """Airlines-shaped n×12 float32 + binomial ``dep_delayed``, from a seed."""
    from h2o3_tpu.frame.frame import Frame
    rng = np.random.default_rng(13)
    X = rng.normal(size=(n, 12)).astype(np.float32)
    logit = X[:, :5] @ np.array([0.8, -0.5, 0.3, -0.2, 0.4], np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit)))
    cols = {f"x{i}": X[:, i] for i in range(12)}
    cols["dep_delayed"] = np.where(y, "YES", "NO")
    return Frame.from_arrays(cols)


def _dl_frame(n: int):
    """MNIST-shaped n×784 float32 + 10-class ``y``, from a seed."""
    from h2o3_tpu.frame.frame import Frame
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, 784)).astype(np.float32)
    yv = rng.integers(0, 10, size=n)
    cols = {f"p{i}": X[:, i] for i in range(784)}
    cols["y"] = np.array([str(d) for d in yv], dtype=object)
    return Frame.from_arrays(cols)


# -- stage 0: the device ------------------------------------------------------

def stage_device(dry: bool) -> dict:
    """The device block and versions — or exit, off-chip, before any work."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not dry:
        sys.exit(f"chip_smoke.py: needs a TPU, JAX found platform="
                 f"{dev.platform!r} ({dev.device_kind}); --dry-cpu runs the "
                 "same stages at toy sizes on CPU")
    if dry and dev.platform != "cpu":
        sys.exit("chip_smoke.py: --dry-cpu is the CPU rehearsal "
                 f"(JAX_PLATFORMS=cpu); JAX found {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    log(f"device {device} versions {versions}")
    if not dry:
        from h2o3_tpu.utils.costs import backend_peak
        peak = backend_peak()
        # the library assumes no peak for a device it cannot name; the
        # smoke does not run on one
        assert peak is not None and peak["name"] == "TPU v5e", (
            f"no v5e peak row for device_kind {dev.device_kind!r}: {peak}")
    return {"device": device, "versions": versions}


# -- stage 1: the kernel against its reference ---------------------------------

def stage_kernel(rows: int, dry: bool) -> dict:
    """``hist_pallas`` against ``_level_histograms`` (the XLA segment-sum
    ground truth) at both ends of the bin-storage envelope."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree import _level_histograms
    from h2o3_tpu.ops import pallas_hist
    assert pallas_hist._INTERPRET is dry, pallas_hist._INTERPRET
    assert pallas_hist._MXU_MODE == "hilo", pallas_hist._MXU_MODE
    out = {}
    for n_nodes, n_bins_tot, dtype in ((32, 65, np.int8), (64, 257, np.int16)):
        rng = np.random.default_rng(n_nodes)
        binned = jnp.asarray(
            rng.integers(0, n_bins_tot, size=(rows, NFEAT)).astype(dtype))
        node = jnp.asarray(rng.integers(-1, n_nodes, size=rows)
                           .astype(np.int32))
        g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
        h = jnp.asarray(rng.random(rows).astype(np.float32) + 0.1)
        w = jnp.ones(rows, jnp.float32)
        want = jax.jit(functools.partial(
            _level_histograms, n_nodes=n_nodes, n_bins_tot=n_bins_tot))(
            binned, node, g, h, w)
        got = pallas_hist.hist_pallas(binned.T, node, g, h, w, n_nodes,
                                      n_bins_tot)
        want, got = np.asarray(want), np.asarray(got)
        assert got.shape == (NFEAT, n_nodes * n_bins_tot, 3), got.shape
        np.testing.assert_allclose(got, want, rtol=HIST_RTOL, atol=HIST_ATOL)
        err = float(np.max(np.abs(got - want) / (np.abs(want) + 1.0)))
        out[f"N{n_nodes}_B{n_bins_tot}_{np.dtype(dtype).name}"] = err
        log(f"kernel N={n_nodes} bins={n_bins_tot} {np.dtype(dtype).name} "
            f"max scaled err {err:.2e}")
    return out


# -- stage 2: train -----------------------------------------------------------

def _check_spread(fr) -> dict:
    """On several devices: every column covers every device with equal row
    counts, and nothing piled up on device 0."""
    import jax
    devs = jax.devices()
    for name in fr.names:
        data = fr.vec(name).data
        shards = data.addressable_shards
        assert {s.device for s in shards} == set(devs), name
        assert len({s.data.shape[0] for s in shards}) == 1, name
    out = {"rows_per_device": int(shards[0].data.shape[0])}
    stats = [d.memory_stats() for d in devs]
    if all(stats):
        in_use = [int(s["bytes_in_use"]) for s in stats]
        assert max(in_use) <= 2 * min(in_use), in_use
        out["bytes_in_use_after_upload"] = in_use
    return out


def _hist_paths(want_kernel: bool) -> dict:
    """The trace-time path counts of the build just traced: a tree's levels
    and its last level's per-node totals. On one device every one with a
    ``_plan`` takes the kernel and none the scatter; over a mesh the levels
    fuse and the totals stay three scatter-adds."""
    from h2o3_tpu.models.tree import HIST_PATHS
    paths = {k: HIST_PATHS[k] for k in ("pallas", "fused_scatter", "scatter")}
    assert sum(paths.values()) == DEPTH + 1, paths
    if want_kernel:
        assert paths == {"pallas": DEPTH + 1, "fused_scatter": 0,
                         "scatter": 0}, paths
    return paths


def stage_train(rows: int, dry: bool) -> tuple[object, dict]:
    import jax

    from h2o3_tpu.models.gbm import GBM, _boost_scan_jit
    from h2o3_tpu.models.tree import HIST_PATHS
    from h2o3_tpu.models.xgboost import XGBoost
    from h2o3_tpu.parallel.mesh import bind_mesh, slice_meshes
    from h2o3_tpu.utils.registry import DKV

    ndev = jax.device_count()
    walls: dict = {}
    out: dict = {"rows": rows, "cold_wall_s": walls}
    fr = timed(walls, "train:frame", _higgs_frame, rows)
    assert fr.nrows == rows and fr.ncols == NFEAT + 1
    if ndev > 1:
        out["spread"] = _check_spread(fr)

    def gbm():
        return GBM(ntrees=5, max_depth=DEPTH, nbins=64, learn_rate=0.1,
                   seed=42).train(y="y", training_frame=fr)

    def checked(model, name):
        assert DKV.get(model.key) is model, f"{name}: model not in DKV"
        auc = float(model.training_metrics.auc)
        assert np.isfinite(auc) and auc > 0.70, f"{name}: AUC {auc}"
        assert model.output["ntrees"] == int(model.params["ntrees"])
        return round(auc, 5)

    HIST_PATHS.clear()
    model = timed(walls, "train:gbm", gbm)
    out["gbm_hist_paths"] = _hist_paths(ndev == 1)
    out["gbm_auc"] = checked(model, "gbm")
    hlo = _boost_scan_jit.executables()[-1].as_text()
    if ndev > 1:
        assert "all-reduce" in hlo, "no all-reduce in the boost program"
        # the same build on ONE device of this host: the sharded AUC agrees
        # with it, and a one-device slice still takes the kernel
        HIST_PATHS.clear()
        with bind_mesh(slice_meshes(ndev)[0]):
            one = timed(walls, "train:gbm_one_device", gbm)
        out["gbm_hist_paths_one_device"] = _hist_paths(True)
        out["gbm_auc_one_device"] = checked(one, "gbm on one device")
        assert abs(out["gbm_auc"] - out["gbm_auc_one_device"]) <= 1e-3, out
    elif not dry:
        # the kernel is in the executable that ran, once per level and once
        # for the last level's totals
        assert hlo.count("tpu_custom_call") >= DEPTH + 1, \
            hlo.count("tpu_custom_call")

    # the XGBoost configuration: 256 bins — the int16 / 257-bin envelope
    HIST_PATHS.clear()
    xgb = timed(walls, "train:xgboost", lambda: XGBoost(
        ntrees=2, max_depth=DEPTH, max_bin=256, eta=0.3,
        seed=42).train(y="y", training_frame=fr))
    out["xgboost_hist_paths"] = _hist_paths(ndev == 1)
    out["xgboost_auc"] = checked(xgb, "xgboost")
    return model, out


# -- stage 3: serve -----------------------------------------------------------

def stage_serve(model) -> dict:
    """``POST /3/Score`` over HTTP against ``model.predict`` on the same
    rows, one request in each batch bucket: equal to ``SCORE_ATOL``, the
    labels equal wherever the probabilities are bit-equal."""
    from h2o3_tpu.api import H2OClient, H2OServer
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.serving import SCORING
    from h2o3_tpu.serving.scorer import bucket_for

    rng = np.random.default_rng(3)
    n_max = max(SCORE_SIZES)
    X = rng.normal(size=(n_max, NFEAT)).astype(np.float32)
    names = [f"x{i}" for i in range(NFEAT)]
    pred = model.predict(Frame.from_arrays(
        {c: X[:, i] for i, c in enumerate(names)}))
    want_p = np.asarray(pred.vec("ps").to_numpy())[:n_max]
    want_lbl = [str(v) for v in pred.vec("predict").labels()[:n_max]]
    assert np.isfinite(want_p).all()

    max_diff: dict[int, float] = {}
    SCORING.reset()
    server = H2OServer(port=0).start()
    try:
        client = H2OClient(server.url)
        for n in SCORE_SIZES:
            rows = X[:n].astype(float).tolist()
            got = client.score(model.key, rows, columns=names)
            assert got["rows"] == n, got["rows"]
            got_p = np.asarray(got["predictions"]["ps"], np.float32)
            assert got_p.shape == (n,) and np.isfinite(got_p).all(), n
            diff = float(np.max(np.abs(got_p - want_p[:n])))
            assert diff <= SCORE_ATOL, (
                f"/3/Score differs from predict at {n} rows: max abs {diff}")
            same = got_p == want_p[:n]
            got_lbl = np.asarray(got["predictions"]["predict"])
            assert (got_lbl[same] == np.asarray(want_lbl[:n])[same]).all(), n
            max_diff[n] = diff
        stats = client.serving()
    finally:
        server.stop()
        SCORING.reset()
    # every request was answered by a compiled executable (a scorer that
    # does not compile raises; there is no other kind): one per bucket
    cache = stats["cache"]
    buckets = {bucket_for(n) for n in SCORE_SIZES}
    assert cache["signatures"] >= len(buckets), cache
    return {"max_abs_diff_vs_predict": max_diff, "scorer_cache": cache}


# -- stage 4: the other two loops, briefly -------------------------------------

def stage_glm(rows: int) -> dict:
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils.registry import DKV
    fr = _glm_frame(rows)
    m = GLM(family="binomial", max_iterations=5).train(
        y="dep_delayed", training_frame=fr)
    assert DKV.get(m.key) is m
    auc = float(m.training_metrics.auc)
    assert np.isfinite(auc) and auc > 0.70, auc
    return {"rows": rows, "auc": round(auc, 5)}


def stage_dl(rows: int) -> dict:
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.utils.registry import DKV
    fr = _dl_frame(rows)
    m = DeepLearning(hidden=[50, 50], epochs=1, mini_batch_size=128,
                     seed=7).train(y="y", training_frame=fr)
    assert DKV.get(m.key) is m
    logloss = float(m.training_metrics.logloss)
    assert np.isfinite(logloss), logloss
    return {"rows": rows, "logloss": round(logloss, 5)}


# -- main ----------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-cpu", action="store_true",
                    help="the same stages at toy sizes on CPU, the kernel "
                         "in interpret mode (summary carries dry_run: true)")
    dry = ap.parse_args().dry_cpu
    sizes = DRY if dry else FULL

    # in a directory that holds this script and nothing else of the repo
    # the run ends here, before a line is printed
    from h2o3_tpu.utils import compile_cache

    found = stage_device(dry)        # exits here, before any work, off-chip
    import jax
    compile_cache.enable(default_on=True)
    if dry:
        from h2o3_tpu.ops import pallas_hist
        pallas_hist._INTERPRET = True

    walls: dict = {}
    kernel = timed(walls, "kernel", stage_kernel, sizes["kernel_rows"], dry)
    model, train = timed(walls, "train", stage_train, sizes["train_rows"],
                         dry)
    serve = timed(walls, "serve", stage_serve, model)
    glm = timed(walls, "glm", stage_glm, sizes["glm_rows"])
    dl = timed(walls, "deeplearning", stage_dl, sizes["dl_rows"])

    from h2o3_tpu.utils.costs import COSTS
    stats = jax.devices()[0].memory_stats() or {}
    cache = compile_cache.stats()
    compiles = {s["site"]: {"compiles": s["compiles"],
                            "seconds": round(s["compile_seconds"], 2)}
                for s in COSTS.snapshot()["sites"]}
    summary = {
        "ok": True, **found, "dry_run": dry, "cold_wall_s": walls,
        "kernel_max_scaled_err": kernel, "train": train, "serve": serve,
        "glm": glm, "deeplearning": dl,
        "compile_cache": {k: cache[k] for k in
                          ("dir", "hits", "misses", "entries")},
        "compile_cache_boost_scan": cache["by_site"].get("gbm:boost_scan"),
        "compile_s_by_site": compiles,
        "peak_hbm_bytes": stats.get("peak_bytes_in_use"),
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    # the last line is the verdict, these keys and no others
    print(json.dumps({"ok": True, "device": found["device"]}), flush=True)


if __name__ == "__main__":
    main()
