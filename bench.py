"""Headline benchmarks at BASELINE.json spec scale.

All five BASELINE.json configs run:

1. **GBM on HIGGS-shaped 11M rows** (primary metric) — histogram-tree
   training rows*trees/sec/chip. vs_baseline anchor: 1.0M rows·trees/sec/
   device. Context (see ROOFLINE.md for the full accounting): published
   A100 `gpu_hist` rides hardware atomic adds at ~the HBM floor
   (~50-150M rows·trees/s on HIGGS); a v5e has no scatter hardware, and
   the MXU one-hot formulation measured in ROOFLINE.md is its ceiling —
   the anchor marks the competitive-on-this-silicon line, not A100 parity.
2. **XGBoost config** — same data, 256 bins / depth 6 (the reference's
   `tree_method=hist` defaults; h2o-extensions/xgboost).
3. **GLM logistic regression** — 1M×12 normals, IRLS to convergence,
   rows·iters/sec/chip. Not measured on the chip by the driver; the ledger's
   ``glm-airlines-build`` cell is the airline-schema GLM (BASELINE config 1).
4. **DeepLearning MLP** — MNIST-shaped 784-50-50-10 Rectifier, samples/sec/
   chip (reference: 294 samples/s on 1× i7-5820k, dlperf.Rmd:375).
5. **AutoML leaderboard** — wall-clock for a 5-model leaderboard on 100k
   rows (reference config: "AutoML leaderboard on Lending Club").

Prints ONE JSON line: the primary GBM metric with the other configs under
"extra". Data is synthetic (zero-egress image): throughput is shape-bound,
not distribution-bound, so rows/sec is faithful. Reported AUCs are on the
synthetic task (not comparable to published HIGGS numbers); model QUALITY
at this scale is pinned separately by ``tests/test_accuracy_1m.py``, which
holds holdout AUC within 3e-3 of sklearn's HistGradientBoosting on 1M rows.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Smoke mode: every config at toy scale so the whole bench pipeline down to
# the JSON emission runs in seconds on CPU. Numbers are meaningless; the
# artifact shape is the point. Without it the bench needs a TPU
# (_require_tpu) — there is no path that measures off-chip.
SMOKE = os.environ.get("H2O3TPU_BENCH_SMOKE", "") == "1"

ROWS = 4_000 if SMOKE else 11_000_000     # argv[1] overrides (main)
NFEAT = 28
NTREES = 3 if SMOKE else 20
DEPTH = 3 if SMOKE else 6
NBINS = 16 if SMOKE else 64
ANCHOR_ROWS_PER_SEC = 1.0e6  # gpu_hist-class anchor (see module docstring)
DL_REF_SAMPLES_PER_SEC = 294.0  # dlperf.Rmd:375 Rectifier on i7-5820k


def _hardware_fingerprint() -> dict:
    """``extra.hardware``: the exact silicon + software stack this artifact
    was measured on, so cross-round comparisons are self-explaining (the
    r03 no-TPU wobble took a VERDICT post-mortem to attribute; a stamped
    fingerprint makes it one diff). Fields mirror what the compute
    observatory keys its peak table on (utils/costs.py PEAK_TABLE)."""
    import jax
    import jaxlib
    devs = jax.devices()
    return {"backend": jax.default_backend(),
            "device_kind": devs[0].device_kind, "devices": len(devs),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__}


def _steady_state_recompiles(scenario: str, sig0: int) -> dict:
    """Post-warmup recompile probe for a warm steady-state scenario:
    ``sig0`` is ``COSTS.signature_count()`` taken AFTER the scenario's
    warm-up call — any growth by now means the timed, shape-identical
    re-run compiled a fresh signature (the r04→r05 automl wobble class of
    regression). The compute gate refuses to stamp on it."""
    from h2o3_tpu.utils.costs import COSTS
    return {"scenario": scenario,
            "recompiles_steady_state": COSTS.signature_count() - sig0}


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


def bench_gbm(fr, ndev: int) -> dict:
    import jax
    from h2o3_tpu.models.gbm import GBM

    def train():
        return GBM(ntrees=NTREES, max_depth=DEPTH, nbins=NBINS,
                   learn_rate=0.1, seed=42).train(y="y", training_frame=fr)

    from h2o3_tpu.utils.costs import COSTS
    train()  # warm-up: compile every level program
    jax.effects_barrier()
    sig0 = COSTS.signature_count()
    t0 = time.perf_counter()
    model = train()
    jax.effects_barrier()
    dt = time.perf_counter() - t0
    rps = fr.nrows * NTREES / dt / ndev
    return dict(rows_per_sec_chip=round(rps, 1), seconds=round(dt, 2),
                auc=round(float(model.training_metrics.auc), 4),
                **_steady_state_recompiles("gbm_higgs_11m", sig0))


def bench_xgboost(fr, ndev: int) -> dict:
    """XGBoost-config run: 256 bins, depth 6, eta 0.3 (hist defaults)."""
    import jax
    from h2o3_tpu.models.xgboost import XGBoost

    nt = 2 if SMOKE else 10
    bins, depth = (16, 3) if SMOKE else (256, 6)

    def train():
        return XGBoost(ntrees=nt, max_depth=depth, max_bin=bins, eta=0.3,
                       seed=42).train(y="y", training_frame=fr)

    from h2o3_tpu.utils.costs import COSTS
    train()
    jax.effects_barrier()
    sig0 = COSTS.signature_count()
    t0 = time.perf_counter()
    model = train()
    jax.effects_barrier()
    dt = time.perf_counter() - t0
    rps = fr.nrows * nt / dt / ndev
    return dict(rows_per_sec_chip=round(rps, 1), seconds=round(dt, 2),
                auc=round(float(model.training_metrics.auc), 4),
                **_steady_state_recompiles("xgboost_hist_11m", sig0))


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


def bench_glm(ndev: int) -> dict:
    """A logistic GLM on 1M x 12 standard normals (BASELINE config 1 in
    name only: no categorical column, a 13 x 13 Gram). Its number is not
    measured on the chip by the driver; see the ledger's
    ``glm-airlines-build`` for GLM on the airline schema (668 one-hot
    columns), rows·iterations/sec/chip."""
    import jax
    from h2o3_tpu.models.glm import GLM

    n = 5_000 if SMOKE else 1_000_000
    fr = _glm_frame(n)

    def train():
        b = GLM(family="binomial", lambda_=1e-4, max_iterations=30)
        m = b.train(y="dep_delayed", training_frame=fr)
        return m, len(b._iter_devs)

    from h2o3_tpu.utils.costs import COSTS
    train()   # warm-up compiles
    jax.effects_barrier()
    sig0 = COSTS.signature_count()
    t0 = time.perf_counter()
    model, iters = train()
    jax.effects_barrier()
    dt = time.perf_counter() - t0
    return dict(rows_iters_per_sec_chip=round(n * iters / dt / ndev, 1),
                iterations=iters, seconds=round(dt, 2),
                auc=round(float(model.training_metrics.auc), 4),
                **_steady_state_recompiles("glm_airlines_1m", sig0))


def _dl_frame(n: int):
    """MNIST-shaped n×784 float32 + 10-class ``y``, from a seed."""
    from h2o3_tpu.frame.frame import Frame
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, 784)).astype(np.float32)
    yv = rng.integers(0, 10, size=n)
    cols = {f"p{i}": X[:, i] for i in range(784)}
    cols["y"] = np.array([str(d) for d in yv], dtype=object)
    return Frame.from_arrays(cols)


def bench_dl(ndev: int) -> dict:
    """MNIST-shaped MLP 784-50-50-10 Rectifier (dlperf.Rmd config)."""
    import jax
    from h2o3_tpu.models.deeplearning import DeepLearning

    n = 2_000 if SMOKE else 60_000
    fr = _dl_frame(n)

    epochs = 1 if SMOKE else 3

    def train():
        return DeepLearning(hidden=[50, 50], activation="Rectifier",
                            epochs=epochs, mini_batch_size=128, seed=7).train(
            y="y", training_frame=fr)

    from h2o3_tpu.utils.costs import COSTS
    train()
    jax.effects_barrier()
    sig0 = COSTS.signature_count()
    t0 = time.perf_counter()
    train()
    jax.effects_barrier()
    dt = time.perf_counter() - t0
    sps = n * epochs / dt / ndev
    return dict(samples_per_sec_chip=round(sps, 1), seconds=round(dt, 2),
                vs_reference_cpu=round(sps / DL_REF_SAMPLES_PER_SEC, 1),
                **_steady_state_recompiles("dl_mlp_mnist", sig0))


def bench_automl(ndev: int) -> dict:
    """Leaderboard wall-clock: 5 models on 100k rows (Lending-Club-scale).
    Runs parallelism 1/2/4 — overlapped builds now lease DISJOINT mesh
    slices from the MeshScheduler (orchestration/scheduler.py), so par>1
    is real device concurrency, not just host-thread overlap. Per-run
    compile-cache hit/miss counts ride along: the r04→r05 wobble
    (32.6s→42.2s) was recompiles, and the artifact now attributes compile
    time vs overlap per parallelism level with data."""
    from h2o3_tpu.orchestration import AutoML
    from h2o3_tpu.orchestration.scheduler import SLICE_STATS
    from h2o3_tpu.utils import compile_cache

    fr = _higgs_frame(3_000 if SMOKE else 100_000)
    out: dict = {}
    # single-device clouds degrade to one slice, so the par sweep only
    # measures host-thread overlap there — one overlapped pass suffices;
    # with >= 2 devices the sweep measures slice concurrency for real
    pars = (2,) if ndev < 2 else ((1, 2) if (SMOKE or ndev < 4) else (1, 2, 4))
    cc: dict = {}
    sl: dict = {}
    for par in pars:
        c0 = compile_cache.stats()
        SLICE_STATS.reset()
        t0 = time.perf_counter()
        aml = AutoML(max_models=2 if SMOKE else 5, nfolds=0, seed=1,
                     parallelism=par)
        aml.train(y="y", training_frame=fr)
        out[f"seconds_par{par}"] = round(time.perf_counter() - t0, 2)
        out["models"] = len(aml.leaderboard)
        c1 = compile_cache.stats()
        # by_site deltas (CostMeter scope attribution): the r04→r05 wobble
        # could only say "something recompiled" — this names WHICH loop
        by_site = {
            site: {k: st[k] - (c0["by_site"].get(site) or
                               {"hits": 0, "misses": 0})[k]
                   for k in ("hits", "misses")}
            for site, st in c1["by_site"].items()}
        cc[f"par{par}"] = {"cache_hits": c1["hits"] - c0["hits"],
                           "cache_misses": c1["misses"] - c0["misses"],
                           "by_site": {s: d for s, d in by_site.items()
                                       if d["hits"] or d["misses"]}}
        # keyed per par level like compile_cache_per_run — utilization and
        # queue wait are only comparable across par levels if each level
        # keeps its own snapshot
        sl[f"par{par}"] = SLICE_STATS.snapshot()
    out["compile_cache_per_run"] = cc
    out["slices"] = sl
    out["seconds"] = out["seconds_par2"]
    if "seconds_par1" in out:
        out["overlap_speedup"] = round(
            out["seconds_par1"] / max(out["seconds_par2"], 1e-9), 2)
    if "seconds_par4" in out:
        out["slice_speedup_par4"] = round(
            out["seconds_par1"] / max(out["seconds_par4"], 1e-9), 2)
    return out


def _slices_gate(out: dict) -> None:
    """Refuse to stamp when slice scheduling makes AutoML SLOWER: on a real
    multi-device run (>= 4 devices, not smoke), parallelism=4 on
    disjoint slices must not lose to sequential full-mesh builds — a
    regression here means leases serialize or resharding dominates."""
    aml = (out.get("extra") or {}).get("automl_leaderboard_100k") or {}
    p1, p4 = aml.get("seconds_par1"), aml.get("seconds_par4")
    if SMOKE or p1 is None or p4 is None:
        return
    # 10% margin: AutoML wall clock is noisy (the r04→r05 recompile wobble
    # was 29%); the gate catches leases serializing or resharding
    # dominating, not jitter
    if p4 > p1 * 1.10:
        print(f"# bench: REFUSING artifact — automl par4 ({p4}s) slower "
              f"than par1 ({p1}s) on a {out['extra'].get('devices')}-device "
              "run (mesh-slice scheduling regressed)", file=sys.stderr)
        sys.exit(3)


def bench_scoring(ndev: int) -> dict:
    """Serving-path throughput: concurrent closed-loop clients against a
    trained GBM + GLM through ``POST /3/Score`` (compiled, micro-batched —
    docs/SERVING.md) vs the sequential per-request ``/3/Predictions`` path
    on the same 16-row payload. Emits qps, latency p50/p99, mean batch
    size, and the scorer-cache counters — the serving path's perf
    trajectory next to the training path's."""
    import threading

    from h2o3_tpu.api import H2OClient, H2OServer
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import GBM
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.serving import SCORING
    from h2o3_tpu.utils.registry import DKV

    n = 2_000 if SMOKE else 20_000
    rng = np.random.default_rng(31)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    logit = X[:, :3] @ np.array([1.0, -0.7, 0.4], np.float32)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logit)),
                         "yes", "no")
    fr = Frame.from_arrays(cols, key="score_bench_frame")
    DKV.put("score_bench_frame", fr)
    gbm = GBM(ntrees=3 if SMOKE else 10, max_depth=4, seed=3,
              model_id="score_bench_gbm").train(y="y", training_frame=fr)
    glm = GLM(family="binomial", lambda_=1e-4,
              model_id="score_bench_glm").train(y="y", training_frame=fr)

    rows_per_req = 16
    payload = [{f"x{i}": float(X[r, i]) for i in range(8)}
               for r in range(rows_per_req)]
    seq_fr = Frame.from_arrays(
        {f"x{i}": X[:rows_per_req, i] for i in range(8)},
        key="score_bench_rows")
    DKV.put("score_bench_rows", seq_fr)

    server = H2OServer(port=0).start()
    try:
        client = H2OClient(server.url)
        duration = 0.5 if SMOKE else 2.0

        # sequential per-request predict path — the ONLY request-sized flow
        # the stack had before the serving tier (ISSUE 6 motivation): ship
        # the rows as a frame, run a full Model.predict, fetch the
        # prediction frame back, clean up. One closed-loop client.
        import csv
        import io
        import tempfile
        buf = io.StringIO()
        w = csv.writer(buf)
        w.writerow([f"x{i}" for i in range(8)])
        for r in range(rows_per_req):
            w.writerow([repr(float(X[r, i])) for i in range(8)])
        with tempfile.NamedTemporaryFile("w", suffix=".csv",
                                         delete=False) as tf:
            tf.write(buf.getvalue())
            seq_csv = tf.name

        def predict_roundtrip(i: int) -> None:
            fk = client.upload_file(seq_csv, destination_frame=f"seq_{i}")
            pk = client.predict(gbm.key, fk)
            client.frame(pk)                   # fetch predictions back
            client.rm(pk)
            client.rm(fk)

        predict_roundtrip(-1)                  # warm compile (self-cleaning)
        nseq, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < duration:
            predict_roundtrip(nseq)
            nseq += 1
        seq_qps = nseq / (time.perf_counter() - t0)

        # the resident-frame variant (frame already in DKV — no upload, no
        # fetch) isolates the narrowed predict critical section; reported
        # for transparency, not the comparator a request-sized client sees
        DKV.remove(client.predict(gbm.key, "score_bench_rows"))  # warm
        pred_keys, t0 = [], time.perf_counter()
        while time.perf_counter() - t0 < duration:
            pred_keys.append(client.predict(gbm.key, "score_bench_rows"))
        resident_qps = len(pred_keys) / (time.perf_counter() - t0)
        for k in pred_keys:
            DKV.remove(k)

        # batched path: closed-loop thread-pool clients, both models hot.
        # Warm every bucket the pool can reach (nclients * rows_per_req
        # coalesced rows max), so the timed window asserts zero compiles.
        for nb in (1, 2, 4, 8):
            client.score(gbm.key, payload * nb)
            client.score(glm.key, payload * nb)
        cache0 = SCORING.cache.stats()
        from h2o3_tpu.utils.telemetry import SCORE_BATCH_SIZE
        bs0_sum, bs0_cnt = SCORE_BATCH_SIZE._default().sum, \
            SCORE_BATCH_SIZE._default().count
        nclients = 2 if SMOKE else 8
        lat_lock = threading.Lock()
        latencies: list[float] = []
        counts = [0] * nclients
        client_errors: list[BaseException] = []
        stop_at = time.perf_counter() + duration

        def work(i: int) -> None:
            cl = H2OClient(server.url)
            key = gbm.key if i % 2 == 0 else glm.key
            mine = []
            try:
                while time.perf_counter() < stop_at:
                    r0 = time.perf_counter()
                    cl.score(key, payload)
                    mine.append(time.perf_counter() - r0)
                    counts[i] += 1
            except BaseException as e:   # noqa: BLE001 — surfaced after join
                client_errors.append(e)
            finally:
                with lat_lock:
                    latencies.extend(mine)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(nclients)]
        bt0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        bt = time.perf_counter() - bt0
        if client_errors:
            # a dead client thread would silently distort the gated numbers
            raise RuntimeError(
                f"{len(client_errors)} scoring client(s) failed; first: "
                f"{client_errors[0]!r}") from client_errors[0]
        total = sum(counts)
        lat = np.sort(np.array(latencies)) * 1e3
        cache1 = SCORING.cache.stats()
        bs_cnt = SCORE_BATCH_SIZE._default().count - bs0_cnt
        bs_sum = SCORE_BATCH_SIZE._default().sum - bs0_sum
        qps = total / bt
        return dict(
            score_qps=round(qps, 1),
            rows_per_sec=round(qps * rows_per_req, 1),
            latency_ms=dict(
                p50=round(float(np.percentile(lat, 50)), 3),
                p99=round(float(np.percentile(lat, 99)), 3)),
            mean_batch_size=round(bs_sum / max(bs_cnt, 1), 2),
            clients=nclients, rows_per_request=rows_per_req,
            requests=total, seconds=round(bt, 2),
            seq_predict_qps=round(seq_qps, 1),
            predict_resident_qps=round(resident_qps, 1),
            speedup_vs_predict=round(qps / max(seq_qps, 1e-9), 2),
            cache_hits=cache1["hits"] - cache0["hits"],
            cache_misses=cache1["misses"] - cache0["misses"])
    finally:
        server.stop()
        SCORING.reset()
        import contextlib
        import os as _os
        with contextlib.suppress(OSError, NameError):
            _os.unlink(seq_csv)
        # nothing from this scenario stays registered: the later memory
        # section's DKV totals / leak pass must reflect the workloads, not
        # serving-bench residue
        for k in ("score_bench_rows", "score_bench_frame",
                  "score_bench_gbm", "score_bench_glm"):
            DKV.remove(k)


def bench_serving_slo(ndev: int) -> dict:
    """SLO-held serving under open-loop arrivals WITH a concurrent GBM
    build (ISSUE 13 acceptance; docs/SERVING.md "SLO & replicas"): a
    replica pool (slice-leased when the mesh allows) serves a trained GBM
    at a p99 latency target while a second GBM trains in the background
    on the same process, arrivals fire at a fixed rate regardless of
    completions (open loop — queue pressure is real), and a quarter of
    the traffic is LOW priority so the shedding estimator has someone to
    turn away first. Emits p50/p99 vs the target, shed/503 rates by
    priority, per-replica busy/queue-wait, and the warm-window compile
    accounting the gate refuses recompiles on."""
    import queue as _queue
    import threading

    from h2o3_tpu.api import H2OClient, H2OServer
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import GBM
    from h2o3_tpu.serving import SCORING
    from h2o3_tpu.utils.registry import DKV

    target_slo_ms = 500.0 if SMOKE else 250.0
    duration = 1.0 if SMOKE else 3.0
    hi_pri, lo_pri = 8, 1

    n = 2_000 if SMOKE else 20_000
    rng = np.random.default_rng(47)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    logit = X[:, :3] @ np.array([1.0, -0.7, 0.4], np.float32)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logit)),
                         "yes", "no")
    fr = Frame.from_arrays(cols, key="slo_bench_frame")
    DKV.put("slo_bench_frame", fr)
    serve_gbm = GBM(ntrees=3 if SMOKE else 10, max_depth=4, seed=5,
                    model_id="slo_bench_gbm").train(y="y", training_frame=fr)

    SCORING.reset()
    scheduler = None
    if ndev >= 2:
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        scheduler = MeshScheduler(slices=2)
        SCORING.configure_replicas(2, scheduler=scheduler)
    else:
        SCORING.configure_replicas(1)

    rows_per_req = 16
    payload = [{f"x{i}": float(X[r, i]) for i in range(8)}
               for r in range(rows_per_req)]

    server = H2OServer(port=0).start()
    train_err: list = []
    train_done = threading.Event()
    try:
        client = H2OClient(server.url)
        # warm every bucket open-loop bursts can coalesce into (workers
        # cap the burst at nworkers * rows_per_req rows), THEN join the
        # admission pre-compiles, THEN snapshot miss counters: the timed
        # window must compile nothing
        for nb in (1, 2, 4, 8, 16):
            client.score(serve_gbm.key, payload * nb, slo_ms=target_slo_ms)
        entry = SCORING._resident[serve_gbm.key]
        pool = SCORING.pool
        for rep in pool.replicas:
            rep.precompile(entry, buckets=(16, 32, 64, 128, 256)) \
                .join(timeout=300)
        # admission fired its own fire-and-forget precompiles (default
        # buckets) — wait for EVERY warm-up to drain before snapshotting
        # the miss counter, or a straggling compile lands in the timed
        # window and the gate refuses a perfectly warm run
        wdl = time.perf_counter() + 300
        while any(r.warming() for r in pool.replicas) \
                and time.perf_counter() < wdl:
            time.sleep(0.05)
        # FREEZE scaling for the timed window: a mid-window scale-up
        # would precompile buckets into a fresh replica's cache and the
        # monotonic miss counter would read as a warm-path recompile,
        # refusing the artifact spuriously (the scale policy itself is
        # pinned by tests/test_serving_slo.py, not timed here)
        pool.min_replicas = pool.max_replicas = len(pool.replicas)

        def cache_misses() -> int:
            # the process-global MONOTONIC miss counter, not a sum over
            # live caches: a mid-window scale-down clears the retired
            # replica's cache and a per-cache sum would go backwards
            from h2o3_tpu.utils.telemetry import SCORER_CACHE
            return int(SCORER_CACHE.labels(event="miss").value)

        # calibration: sequential warm requests size the open-loop rate
        cal = []
        for _ in range(3 if SMOKE else 10):
            c0 = time.perf_counter()
            client.score(serve_gbm.key, payload)
            cal.append(time.perf_counter() - c0)
        mean_s = max(float(np.mean(cal)), 1e-4)
        # ~1.5x the serial capacity of one seat: enough pressure that the
        # controller and (multi-device) the second replica matter, not so
        # much that the whole window sheds
        rate = min(max(1.5 / mean_s, 10.0), 400.0)

        misses0 = cache_misses()

        # the concurrent GBM build: training contends for the process
        # (and, without slices, the devices) for the whole window
        def train():
            try:
                GBM(ntrees=4 if SMOKE else 12, max_depth=5, seed=9,
                    model_id="slo_bench_train").train(
                        y="y", training_frame=fr)
            except BaseException as e:   # noqa: BLE001 — gate checks
                train_err.append(e)
            finally:
                train_done.set()

        trainer = threading.Thread(target=train, daemon=True)

        # open-loop: a metronome enqueues arrival tokens at `rate`
        # regardless of completions; a worker pool fires them
        arrivals: "_queue.Queue" = _queue.Queue()
        res_lock = threading.Lock()
        lat_ok: list = []
        codes = {"ok_hi": 0, "ok_lo": 0, "shed_hi": 0, "shed_lo": 0,
                 "other": 0}
        stop = threading.Event()

        def worker():
            cl = H2OClient(server.url)
            while True:
                try:
                    pri = arrivals.get(timeout=0.25)
                except _queue.Empty:
                    if stop.is_set():
                        return
                    continue
                r0 = time.perf_counter()
                try:
                    cl.score(serve_gbm.key, payload, priority=pri,
                             slo_ms=target_slo_ms)
                    dt = time.perf_counter() - r0
                    with res_lock:
                        lat_ok.append(dt)
                        codes["ok_hi" if pri == hi_pri else "ok_lo"] += 1
                except RuntimeError as e:
                    with res_lock:
                        if "503" in str(e):
                            codes["shed_hi" if pri == hi_pri
                                  else "shed_lo"] += 1
                        else:
                            codes["other"] += 1
                except BaseException:   # noqa: BLE001 — accounted
                    with res_lock:
                        codes["other"] += 1

        nworkers = 4 if SMOKE else 16
        workers = [threading.Thread(target=worker, daemon=True)
                   for _ in range(nworkers)]
        trainer.start()
        for w in workers:
            w.start()
        period = 1.0 / rate
        t0 = time.perf_counter()
        i = 0
        narrivals = 0
        while True:
            now = time.perf_counter()
            if now - t0 >= duration:
                break
            due = t0 + i * period
            if now < due:
                time.sleep(min(due - now, 0.01))
                continue
            # every 4th arrival is low priority: the shed policy's fodder
            arrivals.put(lo_pri if i % 4 == 3 else hi_pri)
            narrivals += 1
            i += 1
        stop.set()
        for w in workers:
            w.join(timeout=60)
        misses_timed = cache_misses() - misses0
        train_done.wait(timeout=600)
        trainer.join(timeout=10)

        lat = np.sort(np.array(lat_ok)) * 1e3 if lat_ok else np.array([])
        served = codes["ok_hi"] + codes["ok_lo"]
        shed = codes["shed_hi"] + codes["shed_lo"]
        st = SCORING.stats()
        entry_row = next((r for r in st["resident"]
                          if r["model"] == serve_gbm.key), None)
        return dict(
            target_slo_ms=target_slo_ms,
            open_loop_rate_rps=round(rate, 1),
            arrivals=narrivals, served=served,
            latency_ms=dict(
                p50=(round(float(np.percentile(lat, 50)), 3)
                     if lat.size else None),
                p99=(round(float(np.percentile(lat, 99)), 3)
                     if lat.size else None)),
            slo=entry_row["slo"] if entry_row else None,
            shed_total=shed,
            shed_rate=round(shed / max(narrivals, 1), 4),
            shed_by_priority={
                str(hi_pri): codes["shed_hi"], str(lo_pri): codes["shed_lo"]},
            served_by_priority={
                str(hi_pri): codes["ok_hi"], str(lo_pri): codes["ok_lo"]},
            server_shed=st["shed"], server_shed_total=st["shed_total"],
            other_errors=codes["other"],
            replicas=st["replicas"],
            cache_misses_timed=misses_timed,
            concurrent_build_completed=train_done.is_set()
            and not train_err,
            concurrent_build_error=(repr(train_err[0]) if train_err
                                    else None))
    finally:
        server.stop()
        SCORING.reset()
        for k in ("slo_bench_frame", "slo_bench_gbm", "slo_bench_train"):
            DKV.remove(k)


def _serving_slo_gate(sl: dict, backend: str) -> None:
    """Refuse to stamp when the SLO serving scenario is broken: the
    concurrent GBM build must complete, shed accounting must not read
    hollow (client-observed 503s and server shed counters must agree
    that shedding did or did not happen), the warm window must compile
    nothing, and on REAL hardware the served p99 must hold the target
    (CPU rounds skip the latency assertion — scheduler noise)."""
    if sl.get("skipped"):
        return
    if sl.get("error"):
        print(f"# bench REFUSED: serving-slo section failed: {sl['error']}",
              file=sys.stderr)
        sys.exit(3)
    if not sl["concurrent_build_completed"]:
        print("# bench REFUSED: concurrent GBM build did not complete "
              f"during the serving window: {sl.get('concurrent_build_error')}",
              file=sys.stderr)
        sys.exit(3)
    if sl["cache_misses_timed"] > 0:
        print(f"# bench REFUSED: {sl['cache_misses_timed']} scorer compiles "
              "inside the timed SLO window — the warm path is recompiling",
              file=sys.stderr)
        sys.exit(3)
    hollow = (sl["shed_total"] > 0) != (sl["server_shed_total"] > 0)
    if hollow:
        print(f"# bench REFUSED: shed accounting reads hollow — clients saw "
              f"{sl['shed_total']} 503s but the server accounted "
              f"{sl['server_shed_total']} sheds", file=sys.stderr)
        sys.exit(3)
    if sl["served"] == 0:
        print("# bench REFUSED: serving-slo window served zero requests",
              file=sys.stderr)
        sys.exit(3)
    real = backend != "cpu"
    if real and not SMOKE:
        p99 = (sl.get("latency_ms") or {}).get("p99")
        if p99 is None or p99 > sl["target_slo_ms"]:
            print(f"# bench REFUSED: served p99 {p99}ms violates the "
                  f"{sl['target_slo_ms']}ms SLO on a real run",
                  file=sys.stderr)
            sys.exit(3)


def _scoring_gate(sc: dict) -> None:
    """Refuse to stamp an artifact whose serving path regressed: under
    concurrent load the batched /3/Score tier must beat the sequential
    per-request predict path by ≥3× (ISSUE 6 acceptance), and warm-path
    requests must not recompile (signature-cache misses after warm-up
    mean the compile cache regressed)."""
    if sc.get("error"):
        print(f"# bench REFUSED: scoring section failed: {sc['error']}",
              file=sys.stderr)
        sys.exit(3)
    if sc["cache_misses"] > 0:
        print(f"# bench REFUSED: {sc['cache_misses']} scorer-cache misses "
              "after warm-up — same-signature requests are recompiling",
              file=sys.stderr)
        sys.exit(3)
    if SMOKE:
        return          # shape-proof only; a 0.5s window is scheduler noise
    if sc["speedup_vs_predict"] < 3.0:
        print(f"# bench REFUSED: batched scoring speedup "
              f"{sc['speedup_vs_predict']}x < 3x over the per-request "
              "predict path", file=sys.stderr)
        sys.exit(3)


def bench_chaos(ndev: int) -> dict:
    """Completion-under-faults (ISSUE 8 acceptance): with ``drop_rate=0.02``
    on the dispatch path, GLM and GBM builds must complete with results
    within 1e-6 of the fault-free run — the retry/backoff layer absorbs the
    injected faults. A dispatch storm under the same injector exercises the
    retry path at volume, and the whole faulted phase runs under a WATCHDOG:
    a deadlocked chaos run records ``completed: false`` (the gate refuses to
    stamp) instead of hanging the bench."""
    import threading

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import GBM
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.ops.map_reduce import map_reduce
    from h2o3_tpu.utils.registry import DKV
    from h2o3_tpu.utils.telemetry import DISPATCH_RETRIES
    from h2o3_tpu.utils.timeline import inject_faults

    n = 2_000 if SMOKE else 50_000
    rng = np.random.default_rng(41)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    logit = X[:, :3] @ np.array([1.0, -0.7, 0.4], np.float32)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logit)),
                         "yes", "no")
    fr = Frame.from_arrays(cols)

    def builds():
        glm = GLM(family="binomial", lambda_=1e-4, max_iterations=15,
                  model_id="chaos_glm").train(y="y", training_frame=fr)
        gbm = GBM(ntrees=8, max_depth=4, seed=11, trees_per_dispatch=2,
                  model_id="chaos_gbm").train(y="y", training_frame=fr)
        pg = np.asarray(jax.device_get(glm._score_raw(fr)))
        pb = np.asarray(jax.device_get(gbm._score_raw(fr)))
        for k in ("chaos_glm", "chaos_gbm"):
            DKV.remove(k)
        return pg, pb

    t0 = time.perf_counter()
    clean_glm, clean_gbm = builds()         # fault-free reference (+ warm-up)
    clean_secs = time.perf_counter() - t0

    def retried_total():
        return sum(c.value for labels, c in DISPATCH_RETRIES.children()
                   if labels["outcome"] == "retried")

    storm = jnp.ones(256, jnp.float32)
    result: dict = {}

    def _storm_sum(s):
        return s.sum()

    def chaos_phase():
        try:
            # dispatch storm: enough dispatches that 2% drops MUST fire and
            # be absorbed (P(zero faults) < 1e-4 at 500 draws); one stable
            # map_fn so the compiled-program cache serves every call
            for _ in range(20 if SMOKE else 500):
                map_reduce(_storm_sum, storm)
            result["glm"], result["gbm"] = builds()
        except BaseException as e:   # noqa: BLE001 — the gate refuses on it
            result["error"] = f"{type(e).__name__}: {e}"

    r0 = retried_total()
    with inject_faults(drop_rate=0.02, delay_rate=0.02, delay_ms=1,
                       seed=17) as inj:
        worker = threading.Thread(target=chaos_phase, daemon=True)
        tc0 = time.perf_counter()
        worker.start()
        # watchdog: generous multiple of the clean wall — a faulted run
        # that exceeds it is treated as deadlocked and refused
        worker.join(timeout=max(20.0, 10.0 * clean_secs + 60.0))
        chaos_secs = time.perf_counter() - tc0
        completed = not worker.is_alive()
    faults = inj.dropped + inj.delayed
    if completed and result.get("error"):
        # the faulted run DIED rather than deadlocked — equally refusable
        return {"error": f"faulted run failed: {result['error']}",
                "faults_injected": faults}
    out = dict(completed=completed,
               faults_injected=faults,
               faults_dropped=inj.dropped, faults_delayed=inj.delayed,
               retries_absorbed=round(retried_total() - r0, 1),
               drop_rate=0.02,
               clean_seconds=round(clean_secs, 2),
               chaos_seconds=round(chaos_secs, 2))
    if completed:
        out["glm_divergence"] = float(np.abs(result["glm"]
                                             - clean_glm).max())
        out["gbm_divergence"] = float(np.abs(result["gbm"]
                                             - clean_gbm).max())
    return out


def _chaos_gate(ch: dict) -> None:
    """Refuse to stamp an artifact whose chaos run deadlocked or diverged:
    a faulted build that hangs means retry/backoff lost a failure (the
    exact regression this layer exists to prevent), and divergence beyond
    1e-6 means a retry re-ran a non-functional dispatch."""
    if ch.get("error"):
        print(f"# bench REFUSED: chaos section failed: {ch['error']}",
              file=sys.stderr)
        sys.exit(3)
    if not ch["completed"]:
        print("# bench REFUSED: chaos run DEADLOCKED — faulted builds did "
              "not complete within the watchdog budget", file=sys.stderr)
        sys.exit(3)
    if ch["glm_divergence"] > 1e-6 or ch["gbm_divergence"] > 1e-6:
        print(f"# bench REFUSED: faulted builds diverged from the "
              f"fault-free run (glm {ch['glm_divergence']}, gbm "
              f"{ch['gbm_divergence']} > 1e-6)", file=sys.stderr)
        sys.exit(3)
    if not SMOKE and ch["faults_injected"] == 0:
        print("# bench REFUSED: chaos phase injected zero faults — the "
              "harness is hollow", file=sys.stderr)
        sys.exit(3)


def bench_elastic(ndev: int) -> dict:
    """Elastic local-SGD under a mid-epoch worker kill (ISSUE 12 / ROADMAP
    item 3 acceptance): a k-worker elastic DL run where one worker is
    stalled dead mid-run must COMPLETE with exactly one ejection, the dead
    worker's shard reassigned to survivors, and the kill costing less than
    the dead worker's throughput share (slowdown < 1/k vs the uninterrupted
    k-worker run — enforced on real hardware; smoke-mode CPU rounds enforce
    completion + bounded wall only, the same policy as the slices gate)."""
    import threading

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.parallel import elastic as _el
    from h2o3_tpu.utils.registry import DKV
    from h2o3_tpu.utils.timeline import inject_faults

    k = 4 if ndev % 4 == 0 else (2 if ndev % 2 == 0 else max(ndev, 2))
    n = 2_000 if SMOKE else 60_000
    epochs, local_steps = (2, 1) if SMOKE else (8, 1)
    rng = np.random.default_rng(23)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    logit = X[:, :3] @ np.array([1.0, -0.7, 0.4], np.float32)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-logit)),
                         "yes", "no")
    fr = Frame.from_arrays(cols)

    def run(model_id, eps=None):
        b = DeepLearning(hidden=[16], epochs=eps or epochs, elastic=k,
                         local_steps=local_steps, mini_batch_size=64,
                         seed=9, model_id=model_id)
        t0 = time.perf_counter()
        m = b.train(y="y", training_frame=fr)
        return m, b.job, time.perf_counter() - t0

    # warm-up pass: compiles every per-slice signature so BOTH timed runs
    # below are warm — without it the clean run carries the one-time
    # compile cost and the slowdown ratio under-reads
    warm_model, _, _ = run("elastic_warm", eps=1)
    spw = warm_model.output["elastic"]["shards_per_worker"]
    # uninterrupted k-worker reference
    clean_model, _, clean_secs = run("elastic_clean")
    clean_rounds = clean_model.output["elastic"]["rounds"]
    round_wall = clean_secs / max(clean_rounds, 1)

    # tight-but-safe membership knobs derived from the measured cadence:
    # the stall outlives the whole run (a dead worker, not a hiccup); the
    # deadline ejects it within <2 rounds BUT must clear the post-ejection
    # round wall — survivors carry ceil(spw·k/(k-1))/spw ≈ 1.33x compute
    # per round after the kill, and a deadline below that would
    # mass-suspect the survivors themselves. The kill lands MID-RUN
    # (worker 1's first sub-shard of round ~mid): `after` counts that
    # worker's own dl_epochs calls, spw per round
    stall_s = max(10.0 * clean_secs, 60.0)
    deadline_s = max(1.75 * round_wall, 1.0)
    kill_round = max(clean_rounds // 2, 1)
    env_save = {kk: os.environ.get(kk) for kk in
                ("H2O3TPU_ELASTIC_ROUND_DEADLINE_SECS",
                 "H2O3TPU_ELASTIC_LEASE_SECS")}
    os.environ["H2O3TPU_ELASTIC_ROUND_DEADLINE_SECS"] = str(deadline_s)
    os.environ["H2O3TPU_ELASTIC_LEASE_SECS"] = str(max(deadline_s / 2, 0.5))
    result: dict = {}

    def killed_phase():
        try:
            result["model"], result["job"], result["secs"] = \
                run("elastic_killed")
        except BaseException as e:   # noqa: BLE001 — the gate refuses on it
            result["error"] = f"{type(e).__name__}: {e}"

    try:
        with inject_faults(worker_rates={1: {"stall_rate": 1.0,
                                             "stall_ms": stall_s * 1e3,
                                             "after": kill_round * spw}}
                           ) as inj:
            worker = threading.Thread(target=killed_phase, daemon=True)
            worker.start()
            # watchdog: a wedged elastic run is the exact regression this
            # layer exists to prevent — refuse instead of hanging the bench
            worker.join(timeout=max(30.0, 5.0 * clean_secs + stall_s / 2))
            completed = not worker.is_alive()
    finally:
        _el.drain(60.0)
        for kk, v in env_save.items():
            if v is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = v

    for key in ("elastic_warm", "elastic_clean", "elastic_killed"):
        DKV.remove(key)
    if result.get("error"):
        return {"error": f"killed run failed: {result['error']}",
                "stalls_injected": inj.stalled}
    out = dict(workers=k, rounds=clean_rounds, local_steps=local_steps,
               shards_per_worker=spw, kill_round=kill_round,
               completed=completed, stalls_injected=inj.stalled,
               clean_seconds=round(clean_secs, 2))
    if completed:
        el = result["model"].output["elastic"]
        killed_secs = result["secs"]
        slowdown = (killed_secs - clean_secs) / max(clean_secs, 1e-9)
        out.update(
            killed_status=result["job"].status,
            killed_seconds=round(killed_secs, 2),
            # what the kill actually cost, vs the dead worker's share
            slowdown_frac=round(slowdown, 4),
            dead_worker_share=round(1.0 / k, 4),
            recovery_latency_s=round(max(killed_secs - clean_secs, 0.0), 2),
            workers_ejected=int(result["job"].workers_ejected),
            ejections_by_reason=el["ejections_by_reason"],
            rounds_killed_run=el["rounds"],
            # per-worker throughput: averaging rounds carried / busy wall
            per_worker={w: {"rounds_done": v["rounds_done"],
                            "busy_seconds": v["busy_seconds"],
                            "rounds_per_sec": round(
                                v["rounds_done"]
                                / max(v["busy_seconds"], 1e-9), 3),
                            "state": v["state"]}
                        for w, v in el["per_worker"].items()},
            final_loss_clean=clean_model.output["score_history"][-1]
            ["train_loss"] if clean_model.output["score_history"] else None,
            final_loss_killed=result["model"].output["score_history"][-1]
            ["train_loss"] if result["model"].output["score_history"]
            else None)
    return out


def _elastic_gate(el: dict, backend: str) -> None:
    """Refuse to stamp when the elastic chaos scenario wedged, ejected the
    wrong number of workers, or (on real hardware) the kill cost more than
    the dead worker's throughput share — ROADMAP item 3's acceptance bar."""
    if el.get("skipped"):
        return
    if el.get("error"):
        print(f"# bench REFUSED: elastic section failed: {el['error']}",
              file=sys.stderr)
        sys.exit(3)
    if not el["completed"]:
        print("# bench REFUSED: elastic killed-worker run WEDGED — the "
              "dead worker stalled the cloud", file=sys.stderr)
        sys.exit(3)
    if el.get("workers_ejected") != 1:
        print(f"# bench REFUSED: elastic kill ejected "
              f"{el.get('workers_ejected')} workers (expected exactly 1) — "
              "the harness is hollow or membership over-reacted",
              file=sys.stderr)
        sys.exit(3)
    if el.get("stalls_injected", 0) < 1:
        print("# bench REFUSED: elastic scenario injected zero stalls",
              file=sys.stderr)
        sys.exit(3)
    if el.get("killed_status") != "DONE":
        # a quorum-cancelled partial would otherwise read as a pass with a
        # trivially-negative slowdown (it trained fewer epochs)
        print(f"# bench REFUSED: killed run ended {el.get('killed_status')} "
              "— survivors did not finish the build", file=sys.stderr)
        sys.exit(3)
    if el.get("rounds_killed_run") != el.get("rounds"):
        print(f"# bench REFUSED: killed run carried "
              f"{el.get('rounds_killed_run')} rounds vs the clean run's "
              f"{el.get('rounds')} — membership over-reacted (mass-suspect "
              "or early exit), the epochs were not all trained",
              file=sys.stderr)
        sys.exit(3)
    real = backend != "cpu"
    if real and el["slowdown_frac"] >= el["dead_worker_share"]:
        print(f"# bench REFUSED: killing 1/{el['workers']} workers cost "
              f"{el['slowdown_frac']:.1%} of throughput (>= its "
              f"{el['dead_worker_share']:.1%} share)", file=sys.stderr)
        sys.exit(3)


def bench_tracing(ndev: int) -> dict:
    """Trace-store overhead + the slowest trace's critical path.

    Trains the same GLM with the tracer ON (under a root span, so every
    IRLS iteration and dispatch records) and OFF (``H2O3TPU_TRACE_OFF=1``),
    min-of-2 each; the ratio is the tracer's wall-time overhead. The
    slowest completed trace's critical path is embedded so the artifact
    carries per-request causality, not just aggregate counters."""
    import jax

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils import tracing as tr

    # real runs time at the 1M airlines scale so the 2% gate compares
    # seconds, not scheduler noise; smoke only proves the plumbing
    n = 3_000 if SMOKE else 1_000_000
    iters = 10 if SMOKE else 25
    rng = np.random.default_rng(23)
    X = rng.normal(size=(n, 12)).astype(np.float32)
    logit = X[:, :5] @ np.array([0.8, -0.5, 0.3, -0.2, 0.4], np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit)))
    cols = {f"x{i}": X[:, i] for i in range(12)}
    cols["resp"] = np.where(y, "YES", "NO")
    fr = Frame.from_arrays(cols)

    def train():
        GLM(family="binomial", lambda_=1e-4, max_iterations=iters).train(
            y="resp", training_frame=fr)

    def timed(traced: bool) -> float:
        t0 = time.perf_counter()
        if traced:
            with tr.TRACER.span("bench:glm_traced", kind="bench", root=True):
                train()
        else:
            os.environ["H2O3TPU_TRACE_OFF"] = "1"
            try:
                train()
            finally:
                os.environ.pop("H2O3TPU_TRACE_OFF", None)
        return time.perf_counter() - t0

    train()                       # warm-up: compiles out of the timed region
    jax.effects_barrier()
    reps = 1 if SMOKE else 2      # min-of-2 damps scheduler noise
    t_on = min(timed(True) for _ in range(reps))
    t_off = min(timed(False) for _ in range(reps))
    overhead = t_on / max(t_off, 1e-9) - 1.0

    traces = tr.TRACER.list_traces()
    bench_traces = [t for t in traces if t["name"] == "bench:glm_traced"]
    out = dict(seconds_traced=round(t_on, 3), seconds_untraced=round(t_off, 3),
               overhead_pct=round(overhead * 100, 2),
               trace_count=len(traces))
    if bench_traces:
        slowest = max(bench_traces, key=lambda t: t["dur_ns"])
        full = tr.TRACER.get_trace(slowest["trace_id"])
        out["slowest_trace"] = dict(
            trace_id=slowest["trace_id"], nspans=slowest["nspans"],
            dur_ms=round(slowest["dur_ns"] / 1e6, 2))
        out["critical_path"] = [
            dict(name=e["name"], kind=e["kind"],
                 dur_ms=round(e["dur_ns"] / 1e6, 2),
                 self_ms=round(e["self_ns"] / 1e6, 2))
            for e in tr.critical_path(full)]
    return out


def bench_ingest(ndev: int) -> dict:
    """Out-of-core ingest proof (ROADMAP item 4, docs/INGEST.md): generate
    a gzip CSV whose UNCOMPRESSED size exceeds a capped host budget, parse
    it through the streaming pipeline (compressed chunks, lazy device
    views), train a GLM on the result, and cycle a spill/fault-in.

    ``extra.ingest`` embeds: peak host RSS growth vs the cap
    (`H2O3TPU_INGEST_RAM_BUDGET` overrides the default of ~60% of the
    dataset's text size), the achieved compression ratio, spill/fault-in
    counters, and a bit-identity check of streamed-vs-eager predictions.
    The gate refuses to stamp a real-run artifact whose ingest RSS growth
    exceeded the cap or whose predictions diverged."""
    import gzip
    import tempfile
    import threading

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.parse import import_file
    from h2o3_tpu.ingest import stream_import
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils import memory as _mem
    from h2o3_tpu.utils.cleaner import (CLEANER, disable_cleaner,
                                        enable_cleaner)
    from h2o3_tpu.utils.registry import DKV

    rows = 30_000 if SMOKE else 8_000_000
    bytes_per_row = 25            # "123,45,67,0.123456,yes" ≈ 25B
    cap = int(os.environ.get("H2O3TPU_INGEST_RAM_BUDGET",
                             str(int(rows * bytes_per_row * 0.6))))
    rng = np.random.default_rng(17)
    tmp = tempfile.mkdtemp(prefix="h2o3_ingest_bench_")
    big = os.path.join(tmp, "big.csv.gz")
    # generate in bounded chunks — the GENERATOR must not hold O(file) either
    text_bytes = 0
    with gzip.open(big, "wt", compresslevel=1) as f:
        f.write("a,b,c,x,y\n")
        left = rows
        while left:
            n = min(left, 100_000)
            a = rng.integers(0, 100, size=n)
            b = rng.integers(-30, 30, size=n)
            c = rng.integers(0, 7, size=n)
            x = rng.normal(size=n)
            ylab = np.where(rng.random(n) < 1 / (1 + np.exp(
                -(0.02 * a - 0.05 * b + 0.3 * x))), "yes", "no")
            block = "\n".join(
                f"{ai},{bi},{ci},{xi:.6f},{yi}"
                for ai, bi, ci, xi, yi in zip(a, b, c, x, ylab)) + "\n"
            text_bytes += len(block)
            f.write(block)
            left -= n

    # RSS sampler: VmHWM is process-lifetime, so sample the live RSS at
    # 50ms cadence across parse+train to get THIS scenario's peak delta
    rss0 = _mem.host_stats()["rss_bytes"]
    peak = {"rss": rss0}
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            peak["rss"] = max(peak["rss"], _mem.host_stats()["rss_bytes"])
            stop.wait(timeout=0.05)

    smp = threading.Thread(target=sampler, daemon=True)
    smp.start()
    out: dict = {"rows": rows, "text_bytes": text_bytes,
                 "gz_bytes": os.path.getsize(big), "cap_bytes": cap,
                 "dataset_exceeds_cap": text_bytes > cap}
    try:
        t0 = time.perf_counter()
        fr = stream_import(big, key="bench_ingest.hex")
        dt = time.perf_counter() - t0
        out["parse_seconds"] = round(dt, 2)
        out["parse_rows_per_sec"] = round(rows / max(dt, 1e-9), 1)
        st = fr._ingest_stats
        out["compression_ratio"] = st["compression_ratio"]
        out["chunks"] = st["chunks"]
        out["inflight_peak_bytes"] = st["inflight_peak_bytes"]
        out["restarts"] = st["restarts"]
        t0 = time.perf_counter()
        model = GLM(family="binomial", lambda_=1e-4, max_iterations=10,
                    seed=5).train(y="y", training_frame=fr)
        out["train_seconds"] = round(time.perf_counter() - t0, 2)
        out["auc"] = round(float(model.training_metrics.auc), 4)
        # the RSS cap covers PARSE+TRAIN — stop sampling before the forced
        # spill cycle below: tier-3 save_frame decodes every column into
        # one npz write (a documented O(file) limitation of the snapshot
        # format, ROADMAP item 4), which would trip the gate on a spike
        # that is not an ingest regression
        stop.set()
        smp.join(timeout=5.0)
        # spill/fault-in cycle: a budget well under even the COMPRESSED
        # payload forces a disk spill (view drops alone can't satisfy it);
        # the re-get faults the frame back in
        sp0 = CLEANER.stats()
        enable_cleaner(max(fr.nbytes // 16, 1), ice_root=os.path.join(
            tmp, "ice"))
        try:
            DKV.put("bench_ingest_hot.hex",
                    Frame.from_arrays({"z": np.zeros(1024, np.float32)},
                                      key="bench_ingest_hot.hex"))
            _ = DKV["bench_ingest.hex"]     # transparent fault-in
        finally:
            disable_cleaner()
        sp1 = CLEANER.stats()
        out["spills"] = sp1["spill_count"] - sp0["spill_count"]
        out["fault_ins"] = sp1["restore_count"] - sp0["restore_count"]
        out["view_drops"] = sp1["view_drops"] - sp0["view_drops"]
    finally:
        stop.set()
        smp.join(timeout=5.0)
    out["rss_peak_delta_bytes"] = max(peak["rss"] - rss0, 0)
    out["under_cap"] = out["rss_peak_delta_bytes"] <= cap

    # bit-identity: streamed+compressed vs eager resident on a subset file
    sub = os.path.join(tmp, "sub.csv")
    with gzip.open(big, "rt") as fin, open(sub, "w") as fout:
        for i, line in enumerate(fin):
            if i > 50_000:
                break
            fout.write(line)
    fs = stream_import(sub, key="bench_ingest_s.hex", chunk_rows=8192)
    fe = import_file(sub, key="bench_ingest_e.hex")
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=8, seed=5)
    ps = GLM(**kw).train(y="y", training_frame=fs).predict(fs) \
        .vec("pyes").to_numpy()
    pe = GLM(**kw).train(y="y", training_frame=fe).predict(fe) \
        .vec("pyes").to_numpy()
    out["bit_identical"] = bool(np.array_equal(ps, pe))
    for k in ("bench_ingest.hex", "bench_ingest_hot.hex",
              "bench_ingest_s.hex", "bench_ingest_e.hex"):
        DKV.remove(k)
    import shutil
    shutil.rmtree(tmp, ignore_errors=True)
    return out


def _ingest_gate(ing: dict) -> None:
    """Refuse to stamp when the out-of-core contract broke: streamed/
    compressed predictions diverging from the eager path is a correctness
    regression on ANY backend; a real run whose ingest RSS growth exceeded
    the configured cap lost the O(chunk)+compressed memory story the
    subsystem exists for (smoke mode annotates only — on CPU device arrays live
    in RSS there, so the cap is not meaningful)."""
    if ing.get("error"):
        print(f"# bench REFUSED: ingest section failed: {ing['error']}",
              file=sys.stderr)
        sys.exit(3)
    if not ing.get("bit_identical"):
        print("# bench REFUSED: streamed/compressed GLM predictions "
              "diverge from the eager resident path", file=sys.stderr)
        sys.exit(3)
    if SMOKE:
        return
    if not ing.get("dataset_exceeds_cap"):
        print("# bench REFUSED: ingest dataset no longer exceeds the RAM "
              "cap — the out-of-core scenario proves nothing",
              file=sys.stderr)
        sys.exit(3)
    if not ing.get("under_cap"):
        print(f"# bench REFUSED: ingest host RSS growth "
              f"{ing['rss_peak_delta_bytes']} exceeds the "
              f"H2O3TPU_INGEST_RAM_BUDGET cap {ing['cap_bytes']}",
              file=sys.stderr)
        sys.exit(3)


def bench_memory() -> dict:
    """Memory accounting for the artifact: host/device watermarks over the
    whole bench run, DKV byte totals by kind, and a leak-detector pass over
    the workload's resident keys (enough sweeps for the detector to express
    an opinion; nothing should flag on a clean run)."""
    from h2o3_tpu.utils import memory as _mem

    _mem.MEMORY.refresh()      # reconcile in-place mutation before sweeping
    rss, dev = _mem.MEMORY.sample()
    # one more observation of the final state, then capture GROWTH flags
    # BEFORE the idle passes below: a static post-workload sweep resets
    # growth streaks by definition, so reading them later would make the
    # gate unreachable
    _mem.MEMORY.leak_sweep()
    growing = [f for f in _mem.MEMORY.leak_report()["flagged"]
               if "growing" in f["reasons"]]
    for _ in range(_mem.MEMORY.detector.sweeps + 1):
        _mem.MEMORY.leak_sweep()
    rep = _mem.MEMORY.leak_report()
    wm = _mem.MEMORY.watermarks
    total, by_kind, nkeys = _mem.MEMORY.dkv_totals()
    return dict(host_rss_bytes=rss,
                host_rss_peak_bytes=wm["host_rss_peak_bytes"],
                device_bytes_in_use=dev,
                device_peak_bytes=wm["device_peak_bytes"],
                device_source=_mem.device_stats()["source"],
                dkv_bytes=total, dkv_by_kind=by_kind, dkv_keys=nkeys,
                leak_sweeps=rep["sweeps"],
                leak_growing=growing,
                leak_flagged=rep["flagged"])


def _memory_gate(memsec: dict) -> None:
    """Refuse to stamp an artifact when the leak detector fires on a real
    run (keys growing or idle-resident above the floor across sweeps are
    exactly what pages someone at 3am), or when the meter itself reads
    hollow — a zero host watermark means the accounting regressed."""
    if memsec.get("error"):
        print(f"# bench REFUSED: memory section failed: {memsec['error']}",
              file=sys.stderr)
        sys.exit(3)
    if SMOKE:
        return          # annotate-only (smoke proves shape; /proc may be absent)
    if memsec["host_rss_peak_bytes"] <= 0:
        print("# bench REFUSED: memory meter reports a zero host watermark "
              "— byte accounting is broken", file=sys.stderr)
        sys.exit(3)
    # gate on GROWTH flags only (captured by bench_memory BEFORE its idle
    # passes — those manufacture idle streaks by construction and would
    # reset growth streaks): bytes that kept rising across the interleaved
    # workload sweeps are the real signal; idle-only flags still ride in
    # the artifact for inspection.
    growing = memsec["leak_growing"]
    if growing:
        for f in growing:
            print(f"# leak: {f}", file=sys.stderr)
        print(f"# bench REFUSED: leak detector flagged {len(growing)} "
              "growing key(s) on a real run", file=sys.stderr)
        sys.exit(3)


def bench_health(ndev: int) -> dict:
    """Ops-plane proof (ISSUE 15): the health evaluator watching a CLEAN
    GLM run must report every subsystem healthy and open ZERO incidents
    (a trip here means a rule's threshold pages on normal operation — the
    boy-who-cried-wolf failure), the sweep thread must have actually swept
    (a hollow watchdog that never ran also reads "healthy"), and the
    evaluator's wall overhead vs ``H2O3TPU_HEALTH_OFF=1`` must stay under
    the same 2% always-on budget the tracer holds."""
    import jax

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils.health import HealthEvaluator
    from h2o3_tpu.utils.incidents import INCIDENTS

    n = 3_000 if SMOKE else 1_000_000
    iters = 10 if SMOKE else 25
    rng = np.random.default_rng(31)
    X = rng.normal(size=(n, 12)).astype(np.float32)
    logit = X[:, :5] @ np.array([0.8, -0.5, 0.3, -0.2, 0.4], np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit)))
    cols = {f"x{i}": X[:, i] for i in range(12)}
    cols["resp"] = np.where(y, "YES", "NO")
    fr = Frame.from_arrays(cols)

    def train():
        GLM(family="binomial", lambda_=1e-4, max_iterations=iters).train(
            y="resp", training_frame=fr)

    train()                       # warm-up: compiles out of the timed region
    jax.effects_barrier()
    # the watched/off comparison needs the knob in both positions; an
    # operator-exported H2O3TPU_HEALTH_OFF=1 must come back afterwards
    saved_off = os.environ.pop("H2O3TPU_HEALTH_OFF", None)

    def timed_watched() -> tuple:
        ev = HealthEvaluator(interval_s=0.05)
        opened0 = INCIDENTS.opened_total()
        ev.evaluate()             # baseline window deltas pre-run
        ev.start()
        t0 = time.perf_counter()
        train()
        wall = time.perf_counter() - t0
        # hollow-watchdog proof: the THREAD must demonstrably sweep (the
        # two inline evaluate() calls here don't count) — a bounded wait
        # OUTSIDE the timed window so sub-interval smoke runs still see it
        deadline = time.monotonic() + 5.0
        while ev.thread_sweeps() < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        verdict = ev.evaluate()   # one final sweep over the finished run
        ev.stop()
        return (wall, verdict, INCIDENTS.opened_total() - opened0,
                ev.thread_sweeps())

    def timed_off() -> float:
        os.environ["H2O3TPU_HEALTH_OFF"] = "1"
        try:
            t0 = time.perf_counter()
            train()
            return time.perf_counter() - t0
        finally:
            os.environ.pop("H2O3TPU_HEALTH_OFF", None)

    reps = 1 if SMOKE else 2      # min-of-N damps scheduler noise
    try:
        watched = [timed_watched() for _ in range(reps)]
        t_on = min(w[0] for w in watched)
        t_off = min(timed_off() for _ in range(reps))
    finally:
        if saved_off is not None:
            os.environ["H2O3TPU_HEALTH_OFF"] = saved_off
    # the gate must see EVERY rep, not the last: an incident tripped in
    # rep 1 that clears by rep 2 is still a rule paging on normal
    # operation — sum the opens, keep the WORST verdict, and require the
    # thread to have swept in every rep
    rank = {"healthy": 0, "degraded": 1, "unhealthy": 2}
    verdict = max((w[1] for w in watched), key=lambda v: rank[v["status"]])
    opened = sum(w[2] for w in watched)
    thread_sweeps = min(w[3] for w in watched)
    overhead = t_on / max(t_off, 1e-9) - 1.0
    return dict(
        seconds_watched=round(t_on, 3), seconds_off=round(t_off, 3),
        overhead_pct=round(overhead * 100, 2),
        status=verdict["status"],
        subsystems={s: v["status"]
                    for s, v in verdict["subsystems"].items()},
        findings=verdict["findings"],
        sweeps=thread_sweeps, incidents_opened=opened,
        open_incidents=verdict["open_incidents"],
        rules=len(verdict["rules"]))


def _health_gate(hl: dict) -> None:
    """Refuse to stamp when the ops plane is hollow or noisy: a clean run
    that trips ANY incident means a rule pages on normal operation; a
    sweep count of zero means the watchdog thread never actually watched;
    >2% overhead on real runs breaks the always-on budget."""
    if hl.get("error"):
        print(f"# bench REFUSED: health section failed: {hl['error']}",
              file=sys.stderr)
        sys.exit(3)
    if hl["sweeps"] <= 0:
        # thread-driven sweeps only — the section's own inline evaluate()
        # calls don't count as the watchdog having watched
        print("# bench REFUSED: health sweep thread never swept — the "
              "watchdog is hollow", file=sys.stderr)
        sys.exit(3)
    if hl["incidents_opened"] > 0 or hl["status"] != "healthy":
        for f in hl["findings"]:
            print(f"# health finding: {f}", file=sys.stderr)
        print(f"# bench REFUSED: clean run reads {hl['status']} with "
              f"{hl['incidents_opened']} incident(s) opened — a health "
              "rule pages on normal operation", file=sys.stderr)
        sys.exit(3)
    if not SMOKE and hl["overhead_pct"] > 2.0:
        print(f"# bench REFUSED: health evaluator overhead "
              f"{hl['overhead_pct']}% exceeds the 2% always-on budget",
              file=sys.stderr)
        sys.exit(3)


def bench_ops(ndev: int) -> dict:
    """Self-driving ops proof (ISSUE 16): replay the three chaos classes
    with remediation switched to ACT mode — each must heal with NO human
    intervention: the health rule trips, the incident rising edge fires
    the engine, exactly ONE bounded audited action of the right class
    lands on the live target, and the incident resolves on the next clean
    sweep. Then a CLEAN GLM run under the same act mode must take ZERO
    actions — an engine that remediates normal operation is worse than no
    engine. Spill-thrash and the stalled worker run fully live (real
    Cleaner/DKV ping-pong, real ElasticGroup with a wedged thread); the
    serving replay injects the shed counters but the action still lands
    on the REAL scoring tier's admission targets."""
    import shutil
    import tempfile
    import threading

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.ops_plane.actions import ActionLog
    from h2o3_tpu.ops_plane.remediate import RemediationEngine
    from h2o3_tpu.utils import health as hm
    from h2o3_tpu.utils.health import HealthEvaluator
    from h2o3_tpu.utils.incidents import IncidentLog
    from h2o3_tpu.utils.registry import DKV

    saved_env = {k: os.environ.get(k) for k in
                 ("H2O3TPU_REMEDIATE", "H2O3TPU_OPS_COOLDOWN_SECS",
                  "H2O3TPU_HEALTH_HEARTBEAT_GAP_SECS")}
    os.environ["H2O3TPU_REMEDIATE"] = "act"
    os.environ["H2O3TPU_OPS_COOLDOWN_SECS"] = "0"

    def rig():
        ev = HealthEvaluator(interval_s=9.0,
                             incidents=IncidentLog(capacity=16))
        eng = RemediationEngine(actions=ActionLog())
        eng.install(ev.incidents)
        return ev, eng

    def outcome(ev, eng, rule):
        applied = [r for r in eng.actions.list()
                   if r["outcome"] == "applied"]
        resolved = [r for r in ev.incidents.list(state="resolved")
                    if r["rule"] == rule]
        return dict(
            rule=rule,
            applied_actions=[r["action"] for r in applied],
            healed=bool(resolved) and not ev.incidents.list(state="open"),
            action_stamped=bool(resolved)
            and resolved[0]["action_id"] is not None,
            records=eng.actions.recorded_total())

    out: dict = {}

    # -- chaos 1: spill-thrash, fully live -----------------------------------
    # two frames + a budget that fits only one → every touch of the cold
    # one restores it and spills the other; the remediation's 1.5× budget
    # raise makes BOTH fit, so the ping-pong goes quiet and the incident
    # resolves on the evidence of the real Cleaner counters
    from h2o3_tpu.utils.cleaner import CLEANER, disable_cleaner, enable_cleaner
    ice = tempfile.mkdtemp(prefix="ops_bench_ice_")
    rng = np.random.default_rng(61)
    ev, eng = rig()
    try:
        frames = {}
        for key in ("ops_thrash_a", "ops_thrash_b"):
            fr = Frame.from_arrays(
                {f"c{i}": rng.normal(size=20_000).astype(np.float32)
                 for i in range(4)}, key=key)
            DKV.put(key, fr)
            frames[key] = fr
        one = frames["ops_thrash_a"].nbytes
        enable_cleaner(int(one * 1.5), ice_root=ice)
        CLEANER.sweep()
        ev.evaluate()                             # window baseline
        for _ in range(4):                        # the thrash
            DKV.get("ops_thrash_a"); CLEANER.sweep()
            DKV.get("ops_thrash_b"); CLEANER.sweep()
        ev.evaluate()                             # trips → engine → budget up
        budget_after = CLEANER.budget
        for _ in range(2):                        # working set fits now
            DKV.get("ops_thrash_a"); CLEANER.sweep()
            DKV.get("ops_thrash_b"); CLEANER.sweep()
        ev.evaluate()                             # quiet window → resolve
        out["spill_thrash"] = dict(
            outcome(ev, eng, "memory_spill_thrash"),
            budget_before=int(one * 1.5), budget_after=budget_after,
            budget_raised=budget_after is not None
            and budget_after > int(one * 1.5))
    finally:
        eng.uninstall()
        for key in ("ops_thrash_a", "ops_thrash_b"):
            try:
                DKV.remove(key)
            except KeyError:
                pass
        disable_cleaner()
        shutil.rmtree(ice, ignore_errors=True)

    # -- chaos 2: serving overload — replayed counters, live admission -------
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.serving.service import SCORING
    SCORING.reset()
    n = 300
    X = rng.normal(size=(n, 3)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.where(X[:, 0] > 0, "yes", "no")
    fr = Frame.from_arrays(cols, key="ops_serve_train")
    glm = GLM(family="binomial", lambda_=1e-4,
              model_id="ops_serve_glm").train(y="y", training_frame=fr)
    rows = [{f"x{i}": float(X[r, i]) for i in range(3)} for r in range(4)]
    SCORING.score(glm.key, rows, slo_ms=50.0)     # resident, target 50ms
    orig_stats, orig_total = hm._serving_stats, hm._score_requests_total
    shed, total = [0.0], [100.0]
    hm._serving_stats = lambda: {
        "shed_total": shed[0],
        "resident": [{"model": glm.key,
                      "slo": {"target_ms": 50.0, "p99_ms": 20.0}}]}
    hm._score_requests_total = lambda: total[0]
    ev, eng = rig()
    try:
        ev.evaluate()                             # baseline
        shed[0], total[0] = 40.0, 200.0           # 40% shed this window
        ev.evaluate()                             # trips → widen admission
        live = orig_stats()                       # REAL tier, post-action
        target_after = next(
            (m["slo"]["target_ms"] for m in live["resident"]
             if m["model"] == glm.key and m.get("slo")), None)
        ev.evaluate()                             # traffic drained → resolve
        out["serving_overload"] = dict(
            outcome(ev, eng, "serving_shed_rate"),
            target_ms_after=target_after,
            admission_widened=bool(target_after and target_after > 50.0))
    finally:
        eng.uninstall()
        hm._serving_stats, hm._score_requests_total = orig_stats, orig_total
        SCORING.reset()
        try:
            DKV.remove("ops_serve_train")
        except KeyError:
            pass

    # -- chaos 3: stalled elastic worker, fully live -------------------------
    # worker 1 wedges mid-round (blocked thread, heartbeat silent); the
    # engine must preempt-reassign its shards BEFORE the 120s lease would
    # have noticed, after which the probe no longer counts the ejected
    # slot and the incident resolves
    from h2o3_tpu.parallel import elastic
    from h2o3_tpu.parallel.elastic import ElasticGroup
    os.environ["H2O3TPU_HEALTH_HEARTBEAT_GAP_SECS"] = "1"
    stall = threading.Event()
    g = ElasticGroup(3, lease_secs=120.0, round_deadline_secs=300.0,
                     group_id="ops_bench_elastic").start()
    thunks = {0: lambda: time.sleep(0.01),
              1: lambda: stall.wait(timeout=60.0),
              2: lambda: time.sleep(0.01)}
    runner = threading.Thread(target=g.run_round, args=(1, thunks),
                              daemon=True)
    ev, eng = rig()
    try:
        runner.start()
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:       # healthy slots heartbeat;
            g.heartbeat(0); g.heartbeat(2)       # the wedged one is silent
            time.sleep(0.1)
        ev.evaluate()                             # gap > 1s → preempt
        membership = g.membership()
        g.heartbeat(0); g.heartbeat(2)
        ev.evaluate()                             # ejected slot not counted
        out["stalled_worker"] = dict(
            outcome(ev, eng, "elastic_heartbeat_gap"),
            worker_ejected=membership.get(1) == "EJECTED",
            survivors=[w for w, s in membership.items() if s == "ACTIVE"])
    finally:
        eng.uninstall()
        stall.set()
        runner.join(timeout=30.0)
        g.shutdown()
        elastic.drain(timeout=10.0)
        if saved_env["H2O3TPU_HEALTH_HEARTBEAT_GAP_SECS"] is None:
            os.environ.pop("H2O3TPU_HEALTH_HEARTBEAT_GAP_SECS", None)

    # -- the negative: a clean run must take ZERO actions --------------------
    nclean = 2_000 if SMOKE else 20_000
    Xc = rng.normal(size=(nclean, 8)).astype(np.float32)
    colsc = {f"x{i}": Xc[:, i] for i in range(8)}
    colsc["y"] = np.where(Xc[:, 0] - Xc[:, 1] > 0, "Y", "N")
    frc = Frame.from_arrays(colsc)

    def clean_train():
        GLM(family="binomial", lambda_=1e-4, max_iterations=8).train(
            y="y", training_frame=frc)

    clean_train()      # warm-up: compiles land OUTSIDE the watched window
    ev, eng = rig()
    try:
        ev.evaluate()                             # baseline
        clean_train()
        ev.evaluate()
        out["clean_run"] = dict(
            actions_taken=eng.actions.recorded_total(),
            incidents_opened=ev.incidents.opened_total())
    finally:
        eng.uninstall()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


_OPS_EXPECTED = {"spill_thrash": "raise_cleaner_budget",
                 "serving_overload": "serving_relief",
                 "stalled_worker": "reassign_shards"}


def _ops_gate(op: dict) -> None:
    """Refuse to stamp unless the remediation engine healed every chaos
    class hands-off — exactly one applied action of the RIGHT class per
    incident, the incident resolved and stamped with the action id — and
    took zero actions on the clean run (a trigger-happy engine pages ops
    with changes nobody asked for)."""
    if op.get("error"):
        print(f"# bench REFUSED: ops section failed: {op['error']}",
              file=sys.stderr)
        sys.exit(3)
    for name, want in _OPS_EXPECTED.items():
        sc = op.get(name) or {}
        if sc.get("applied_actions") != [want]:
            print(f"# bench REFUSED: ops chaos '{name}' applied "
                  f"{sc.get('applied_actions')} — expected exactly one "
                  f"'{want}' action", file=sys.stderr)
            sys.exit(3)
        if not sc.get("healed") or not sc.get("action_stamped"):
            print(f"# bench REFUSED: ops chaos '{name}' did not heal "
                  f"hands-off (healed={sc.get('healed')}, "
                  f"stamped={sc.get('action_stamped')}) — a human would "
                  "have had to step in", file=sys.stderr)
            sys.exit(3)
    clean = op.get("clean_run") or {}
    if clean.get("actions_taken", 1) != 0:
        print(f"# bench REFUSED: remediation took "
              f"{clean.get('actions_taken')} action(s) on a CLEAN run — "
              "the engine remediates normal operation", file=sys.stderr)
        sys.exit(3)


def bench_flight(ndev: int) -> dict:
    """Flight-recorder proof (ISSUE 17): the always-on sampler watching a
    warm GLM must stay under the same 2% overhead budget as the tracer and
    health evaluator (vs ``H2O3TPU_FLIGHT_OFF=1``), its thread must
    demonstrably tick (a hollow recorder also costs 0%), a clean run must
    open ZERO trend incidents and write ZERO post-mortems, an injected
    RSS-growth trend must open exactly ONE trend incident whose context
    carries a non-empty series window, and an injected sweep wedge must
    produce exactly ONE on-disk post-mortem that unpacks with every
    member."""
    import shutil
    import tarfile
    import tempfile

    import jax

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils import blackbox as _bb
    from h2o3_tpu.utils import flight as _fl
    from h2o3_tpu.utils.blackbox import DUMP_MEMBERS, BlackBox
    from h2o3_tpu.utils.health import (HealthEvaluator, default_rules,
                                       trend_window)
    from h2o3_tpu.utils.incidents import IncidentLog
    from h2o3_tpu.utils.timeline import inject_faults

    n = 3_000 if SMOKE else 1_000_000
    iters = 10 if SMOKE else 25
    rng = np.random.default_rng(47)
    X = rng.normal(size=(n, 12)).astype(np.float32)
    logit = X[:, :5] @ np.array([0.8, -0.5, 0.3, -0.2, 0.4], np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit)))
    cols = {f"x{i}": X[:, i] for i in range(12)}
    cols["resp"] = np.where(y, "YES", "NO")
    fr = Frame.from_arrays(cols)

    def train():
        GLM(family="binomial", lambda_=1e-4, max_iterations=iters).train(
            y="resp", training_frame=fr)

    train()                       # warm-up: compiles out of the timed region
    jax.effects_barrier()
    trend_rules = [r for r in default_rules()
                   if r.name.startswith("trend_")]
    # the recorded/off comparison needs the knob in both positions, and the
    # sampler runs at bench cadence; operator exports must come back after
    saved = {k: os.environ.pop(k, None)
             for k in ("H2O3TPU_FLIGHT_OFF", "H2O3TPU_FLIGHT_INTERVAL_SECS",
                       "H2O3TPU_BLACKBOX_STALL_SECS",
                       "H2O3TPU_BLACKBOX_CHECK_SECS")}
    os.environ["H2O3TPU_FLIGHT_INTERVAL_SECS"] = "0.05"
    clean_dir = tempfile.mkdtemp(prefix="h2o3_bench_bb_clean_")
    wedge_dir = tempfile.mkdtemp(prefix="h2o3_bench_bb_wedge_")

    def timed_recorded() -> tuple:
        """One watched rep: global recorder sampling at 20Hz, the four
        trend rules sweeping against it, and an armed black box watching
        the sweep — a clean run must end with zero of each."""
        _fl.FLIGHT.reset()
        _fl.FLIGHT.start()
        ilog = IncidentLog(capacity=8)
        ev = HealthEvaluator(interval_s=0.05, rules=trend_rules,
                             incidents=ilog)
        bb = BlackBox(dump_dir=clean_dir)
        prev_bb = _bb.BLACKBOX
        _bb.BLACKBOX = bb
        try:
            bb.arm()
            bb.watch("health_sweep", period_s=0.05)
            ev.start()
            t0 = time.perf_counter()
            train()
            wall = time.perf_counter() - t0
            # hollow-recorder proof: the sampler THREAD must have ticked;
            # bounded wait OUTSIDE the timed window for sub-interval smokes
            deadline = time.monotonic() + 5.0
            while _fl.FLIGHT.ticks() < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            ev.evaluate()         # one final sweep over the finished run
            ev.stop()
            bb.disarm()           # ORDERLY shutdown: must never dump
            _fl.FLIGHT.stop()
            return (wall, _fl.FLIGHT.ticks(), _fl.FLIGHT.stats(),
                    ilog.opened_total(), int(bb.fired()))
        finally:
            _bb.BLACKBOX = prev_bb

    def timed_off() -> float:
        os.environ["H2O3TPU_FLIGHT_OFF"] = "1"
        try:
            t0 = time.perf_counter()
            train()
            return time.perf_counter() - t0
        finally:
            os.environ.pop("H2O3TPU_FLIGHT_OFF", None)

    reps = 1 if SMOKE else 2      # min-of-N damps scheduler noise
    try:
        recorded = [timed_recorded() for _ in range(reps)]
        t_on = min(r[0] for r in recorded)
        t_off = min(timed_off() for _ in range(reps))

        # -- injected trend: a rising RSS series must trip exactly one
        # trend incident whose context carries the series window --------
        _fl.FLIGHT.reset()
        nwin = trend_window()
        for i in range(nwin):
            _fl.FLIGHT.ingest("derived.host_rss_bytes", 1e9 * (1 + 0.02 * i),
                              now=float(i))
        tlog = IncidentLog(capacity=8)
        tev = HealthEvaluator(
            interval_s=60.0, incidents=tlog,
            rules=[r for r in trend_rules if r.name == "trend_rss_growth"])
        tev.evaluate()
        tev.evaluate()            # steady state: the edge must not re-fire
        trend_incidents = tlog.opened_total()
        window_points = 0
        for inc in tlog.export():
            win = (inc.get("context") or {}).get("flight_window") or {}
            window_points += len(win.get("samples") or [])
        _fl.FLIGHT.reset()

        # -- injected wedge: a stalled sweep must produce exactly one
        # on-disk post-mortem with every member -------------------------
        os.environ["H2O3TPU_BLACKBOX_STALL_SECS"] = "0.3"
        os.environ["H2O3TPU_BLACKBOX_CHECK_SECS"] = "0.05"
        wb = BlackBox(dump_dir=wedge_dir)
        prev_bb = _bb.BLACKBOX
        _bb.BLACKBOX = wb
        wlog = IncidentLog(capacity=8)
        wev = HealthEvaluator(interval_s=0.05, rules=[], incidents=wlog)
        try:
            wb.arm()
            wb.watch("health_sweep", period_s=0.05)
            with inject_faults(site_rates={"health.sweep": {
                    "stall_rate": 1.0, "stall_ms": 5_000}}):
                wev.start()
                deadline = time.monotonic() + 10.0
                while not wb.fired() and time.monotonic() < deadline:
                    time.sleep(0.05)
            wev.stop()
            wb.disarm()
        finally:
            _bb.BLACKBOX = prev_bb
        wedge_dumps = sorted(os.listdir(wedge_dir))
        wedge_members: list = []
        if len(wedge_dumps) == 1:
            with tarfile.open(os.path.join(wedge_dir, wedge_dumps[0])) as tf:
                # entries are h2o3_postmortem/<member> — compare bare names
                wedge_members = sorted(m.name.split("/", 1)[-1]
                                       for m in tf.getmembers())
    finally:
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
            else:
                os.environ.pop(k, None)
    clean_dumps = sorted(os.listdir(clean_dir))
    shutil.rmtree(clean_dir, ignore_errors=True)
    shutil.rmtree(wedge_dir, ignore_errors=True)
    stats = recorded[0][2]
    overhead = t_on / max(t_off, 1e-9) - 1.0
    return dict(
        seconds_recorded=round(t_on, 3), seconds_off=round(t_off, 3),
        overhead_pct=round(overhead * 100, 2),
        ticks=min(r[1] for r in recorded),
        series=stats.get("series"), samples_total=stats.get("samples_total"),
        dropped_series=stats.get("dropped_series"),
        clean_trend_incidents=sum(r[3] for r in recorded),
        clean_postmortems=len(clean_dumps) + sum(r[4] for r in recorded),
        trend_incidents=trend_incidents,
        trend_window_points=window_points,
        wedge_postmortems=len(wedge_dumps),
        wedge_members=wedge_members,
        expected_members=sorted(["reason.json"]
                                + [name for name, _ in DUMP_MEMBERS]))


def _flight_gate(fl: dict) -> None:
    """Refuse to stamp when the flight recorder is hollow, noisy, or
    blind: zero sampler ticks means nothing was recorded; any trend
    incident or post-mortem on a CLEAN run means the recorder pages on
    normal operation; the injected trend must trip exactly once WITH its
    series window; the injected wedge must leave exactly one complete
    post-mortem; >2% overhead breaks the always-on budget."""
    if fl.get("error"):
        print(f"# bench REFUSED: flight section failed: {fl['error']}",
              file=sys.stderr)
        sys.exit(3)
    if fl["ticks"] <= 0:
        print("# bench REFUSED: flight sampler never ticked — the recorder "
              "is hollow", file=sys.stderr)
        sys.exit(3)
    if fl["clean_trend_incidents"] > 0 or fl["clean_postmortems"] > 0:
        print(f"# bench REFUSED: clean run opened "
              f"{fl['clean_trend_incidents']} trend incident(s) and wrote "
              f"{fl['clean_postmortems']} post-mortem(s) — the recorder "
              "pages on normal operation", file=sys.stderr)
        sys.exit(3)
    if fl["trend_incidents"] != 1 or fl["trend_window_points"] <= 0:
        print(f"# bench REFUSED: injected RSS-growth trend opened "
              f"{fl['trend_incidents']} incident(s) with "
              f"{fl['trend_window_points']} window point(s) — expected "
              "exactly one with a non-empty series window",
              file=sys.stderr)
        sys.exit(3)
    missing = set(fl["expected_members"]) - set(fl["wedge_members"])
    if fl["wedge_postmortems"] != 1 or missing:
        print(f"# bench REFUSED: injected sweep wedge produced "
              f"{fl['wedge_postmortems']} post-mortem(s), missing members "
              f"{sorted(missing)} — expected exactly one with every member",
              file=sys.stderr)
        sys.exit(3)
    if not SMOKE and fl["overhead_pct"] > 2.0:
        print(f"# bench REFUSED: flight recorder overhead "
              f"{fl['overhead_pct']}% exceeds the 2% always-on budget",
              file=sys.stderr)
        sys.exit(3)


def _tracing_gate(trc: dict) -> None:
    """Refuse to stamp an artifact whose tracing section is hollow: an
    empty trace store after an instrumented run means the span plumbing
    regressed, and >2% tracer overhead on the traced GLM breaks the
    always-on contract (enforced on real runs; smoke captures
    annotate only — sub-second CPU runs put 2% under scheduler noise)."""
    if trc.get("error"):
        print(f"# bench REFUSED: tracing section failed: {trc['error']}",
              file=sys.stderr)
        sys.exit(3)
    if trc["trace_count"] == 0 or not trc.get("critical_path"):
        print("# bench REFUSED: trace store empty after an instrumented "
              "run — span recording is broken", file=sys.stderr)
        sys.exit(3)
    if not SMOKE and trc["overhead_pct"] > 2.0:
        print(f"# bench REFUSED: tracer overhead {trc['overhead_pct']}% "
              "exceeds the 2% always-on budget", file=sys.stderr)
        sys.exit(3)


def _require_tpu() -> None:
    """Exit non-zero with one line unless JAX's first device is a TPU. No
    child probe, no re-exec, no platform override: a chip belongs to one
    process, and a measurement path that finds no chip fails."""
    import jax
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:      # the backend did not start
        sys.exit("bench.py: no TPU — JAX backend failed to start: "
                 + (str(e).strip().splitlines() or ["?"])[0][:300])
    if dev.platform != "tpu":
        sys.exit(f"bench.py: needs a TPU, JAX found platform="
                 f"{dev.platform!r} ({dev.device_kind}); set "
                 "H2O3TPU_BENCH_SMOKE=1 for the toy-scale pipeline check")


def _lint_gate() -> None:
    """Refuse to stamp a perf artifact from a tree carrying non-baselined
    graftlint findings: a new host-sync / lock-discipline / REST violation
    is exactly the class of regression the numbers are meant to certify
    against. Override with H2O3TPU_BENCH_SKIP_LINT=1 (diagnostics only)."""
    if os.environ.get("H2O3TPU_BENCH_SKIP_LINT", "") == "1":
        return
    from pathlib import Path

    from h2o3_tpu.tools.lint import (DEFAULT_BASELINE, load_baseline,
                                     run_lint, split_findings)
    pkg_root = Path(__file__).resolve().parent / "h2o3_tpu"
    new, _old = split_findings(run_lint(pkg_root),
                               load_baseline(DEFAULT_BASELINE))
    if new:
        for f in new:
            print(f"# graftlint: {f.render()}", file=sys.stderr)
        print(f"# bench REFUSED: {len(new)} non-baselined graftlint "
              "finding(s) — fix or baseline them before stamping an "
              "artifact", file=sys.stderr)
        sys.exit(3)


def _latest_prior_artifact(backend: str):
    """(filename, artifact-dict) of the most recent prior ``BENCH_r*.json``
    stamped on the same backend (honoring H2O3TPU_BENCH_BASELINE_EXCLUDE so
    a re-run never self-compares), or ``(None, None)``. Shared by the
    vs_baseline continuity path and the dispatch-audit regression gate."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    prior = (None, None)
    exclude = os.environ.get("H2O3TPU_BENCH_BASELINE_EXCLUDE", "")
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json")),
                       key=lambda p: [int(s) for s in re.findall(r"\d+", p)]):
        if exclude and os.path.basename(path) == exclude:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        art = doc.get("parsed", doc)   # driver wrapper or raw artifact
        if not isinstance(art, dict):
            continue
        val = art.get("value")
        ext = art.get("extra") or {}
        if isinstance(val, (int, float)) and val > 0 \
                and ext.get("backend") == backend:
            prior = (os.path.basename(path), art)
    return prior


def _resolve_vs_baseline(out: dict) -> None:
    """``baseline_source`` names the comparator: the per-chip anchor on a
    chip run, nothing in smoke mode (toy-scale numbers rate nothing)."""
    if SMOKE:
        out["vs_baseline"] = None
        out["baseline_source"] = "none (smoke mode)"
        return
    out["baseline_source"] = \
        f"anchor {ANCHOR_ROWS_PER_SEC:.1e} rows*trees/sec/chip"


def _compute_section(extra: dict) -> dict:
    """``extra.compute`` — the observatory's view of the run the bench just
    measured (utils/costs.py, ``GET /3/Compute``): per-loop achieved FLOP/s
    and utilization (null off the peak table — every CPU round), per-site
    compile counts/seconds, recompile totals, and the per-scenario
    steady-state recompile probes collected above. The ROOFLINE.md
    arithmetic, stamped automatically every round."""
    from h2o3_tpu.utils.costs import COSTS, backend_peak
    snap = COSTS.snapshot()
    steady = {sec["scenario"]: sec["recompiles_steady_state"]
              for sec in extra.values()
              if isinstance(sec, dict) and "recompiles_steady_state" in sec}
    return {
        "peak": backend_peak(),
        "loops": snap["loops"],
        "sites": {s["site"]: {"compiles": s["compiles"],
                              "compile_seconds": s["compile_seconds"],
                              "flops": s["flops"], "bytes": s["bytes"],
                              "signatures": len(s["signatures"]),
                              "recompile_events": len(s["recompile_events"])}
                  for s in snap["sites"]},
        "recompile_events": snap["recompile_events"],
        "steady_state_recompiles": steady,
    }


def _compute_gate(out: dict) -> None:
    """Refuse to stamp when a warm steady-state scenario recompiled after
    its warm-up phase: the timed re-run is shape-identical by construction,
    so signature growth there means executables are churning — the exact
    recompile class behind the r04→r05 automl wobble, now caught at stamp
    time instead of in the next round's VERDICT."""
    if SMOKE:
        return
    steady = out["extra"]["compute"]["steady_state_recompiles"]
    churned = {k: v for k, v in steady.items() if v > 0}
    if churned:
        for scenario, n in churned.items():
            print(f"# steady-state recompile: {scenario} compiled {n} new "
                  "signature(s) during its shape-identical timed run",
                  file=sys.stderr)
        print(f"# bench REFUSED: {len(churned)} warm scenario(s) recompiled "
              "after warm-up — executables churn in steady state",
              file=sys.stderr)
        sys.exit(3)


def _dispatch_audit_section(backend: str) -> dict:
    """Host-sync economy of the convergence loops this bench just ran:
    blocking device→host fetches per logical iteration (GLM IRLS iteration,
    GBM boosting round, DL epoch), read from the
    ``h2o3_dispatches_per_iteration`` gauges the drivers publish, with a
    ``vs_prior`` comparison against the latest prior same-backend artifact
    so the CPU trajectory keeps rating the sync economy round over round."""
    from h2o3_tpu.utils.telemetry import DISPATCHES_PER_ITER
    current = {labels["loop"]: round(child.value, 4)
               for labels, child in DISPATCHES_PER_ITER.children()}
    sec: dict = {"syncs_per_step": current}
    fname, art = _latest_prior_artifact(backend)
    prior = ((art or {}).get("extra") or {}).get("dispatch_audit") or {}
    prior_steps = prior.get("syncs_per_step") or {}
    if prior_steps:
        sec["vs_prior"] = {
            loop: {"prior": prior_steps[loop], "current": cur,
                   "ratio": round(cur / max(prior_steps[loop], 1e-9), 3)}
            for loop, cur in current.items() if loop in prior_steps}
        sec["baseline_source"] = fname
    else:
        sec["vs_prior"] = None
        sec["baseline_source"] = (f"none (no prior {backend} artifact with "
                                  "a dispatch audit)")
    return sec


def _dispatch_gate(out: dict) -> None:
    """Refuse to stamp a real-run artifact whose syncs-per-step count
    REGRESSED versus the previous same-backend round: a loop paying more
    blocking host fetches per iteration than it used to means a
    per-iteration fetch crept back into a hot path — exactly what the
    megastep refactor (ISSUE 7) exists to prevent."""
    if SMOKE:
        return          # toy scale proves artifact shape only
    audit = (out["extra"].get("dispatch_audit") or {})
    regressed = [
        (loop, cmp["prior"], cmp["current"])
        for loop, cmp in (audit.get("vs_prior") or {}).items()
        if cmp["current"] > cmp["prior"] + 1e-6]
    if regressed:
        for loop, prior, cur in regressed:
            print(f"# dispatch regression: {loop} now pays {cur} host "
                  f"syncs/step (prior round: {prior})", file=sys.stderr)
        print(f"# bench REFUSED: {len(regressed)} loop(s) regressed their "
              "syncs-per-step vs the prior same-backend artifact",
              file=sys.stderr)
        sys.exit(3)


def main() -> None:
    if not SMOKE:
        _require_tpu()
    _lint_gate()

    import jax

    # persistent XLA compilation cache (the standard TPU production setup):
    # AutoML's many model configs are compile-bound on a cold process; the
    # cache cuts repeat runs to pure compute. Timed regions below still
    # include a warm-up call, so cold-vs-warm compile state never leaks
    # into the reported rows/sec. Default ON under bench (H2O3TPU_COMPILE_CACHE=0
    # turns it off; JAX_COMPILATION_CACHE_DIR places it); hit/miss counts
    # land in the artifact below.
    from h2o3_tpu.utils import compile_cache
    compile_cache.enable(default_on=True)
    ndev = max(1, len(jax.devices()))

    extra: dict = {}
    fr = _higgs_frame(int(sys.argv[1]) if len(sys.argv) > 1 else ROWS)
    gbm = bench_gbm(fr, ndev)

    # smoke mode proves the artifact SHAPE; the secondary configs only add
    # CPU compile minutes there
    secondary = () if SMOKE else (
        ("xgboost_hist_11m", bench_xgboost, (fr, ndev)),
        ("glm_airlines_1m", bench_glm, (ndev,)),
        ("dl_mlp_mnist", bench_dl, (ndev,)),
        ("automl_leaderboard_100k", bench_automl, (ndev,)))
    # leak-detector generations interleave with the workloads (without an
    # HBM budget the Cleaner never sweeps): a key whose bytes keep RISING
    # across configs accumulates a growth streak that the memory gate
    # refuses — post-hoc back-to-back sweeps alone could never see growth
    from h2o3_tpu.utils.memory import MEMORY
    MEMORY.refresh()
    MEMORY.leak_sweep()
    for name, fn, args in secondary:
        t0 = time.perf_counter()
        extra[name] = fn(*args)     # a failing configuration fails the run
        print(f"# bench: {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        MEMORY.refresh()        # catch in-place growth, not just re-puts
        MEMORY.leak_sweep()

    out = {
        "metric": "gbm_hist_train_rows_per_sec_per_chip",
        "value": gbm["rows_per_sec_chip"],
        "unit": "rows*trees/sec/chip",
        "vs_baseline": round(gbm["rows_per_sec_chip"] / ANCHOR_ROWS_PER_SEC, 3),
        "extra": {"gbm_higgs_11m": gbm, **extra,
                  "backend": jax.default_backend(), "devices": ndev,
                  "rows": fr.nrows, "hardware": _hardware_fingerprint()},
    }
    _resolve_vs_baseline(out)
    # dispatch accounting: blocking host syncs per GLM iteration / GBM round
    # / DL epoch, gated against the prior same-backend round (ISSUE 7 — a
    # reintroduced per-iteration fetch refuses to stamp)
    out["extra"]["dispatch_audit"] = _dispatch_audit_section(
        out["extra"]["backend"])
    _dispatch_gate(out)
    # mesh-slice scheduling: par4 on disjoint slices must beat (or match)
    # sequential full-mesh builds on a real multi-device run
    _slices_gate(out)
    # chaos: completion-under-faults with retry absorption (ISSUE 8) —
    # refuses to stamp when a faulted run deadlocks or diverges
    try:
        ch = bench_chaos(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        ch = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["chaos"] = ch
    _chaos_gate(ch)
    # elastic local-SGD: kill 1 of k workers mid-epoch — must complete with
    # exactly one ejection, and on real hardware the kill must cost less
    # than the dead worker's throughput share (ROADMAP item 3)
    if SMOKE:
        el: dict = {"skipped": "smoke"}
    else:
        try:
            el = bench_elastic(ndev)
        except Exception as e:   # noqa: BLE001 — gate reports, then refuses
            el = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["elastic"] = el
    _elastic_gate(el, out["extra"]["backend"])
    # serving path: score_qps through the compiled/batched /3/Score tier
    # vs the per-request predict path (ISSUE 6: the scoring tier gets the
    # same perf trajectory the training path has)
    try:
        sc = bench_scoring(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        sc = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["scoring"] = sc
    _scoring_gate(sc)
    # SLO-adaptive serving: hold a p99 target under open-loop arrivals
    # with a concurrent GBM build, shed low priority first (ISSUE 13);
    # rides inside extra.scoring as the `slo` block
    try:
        sl = bench_serving_slo(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        sl = {"error": f"{type(e).__name__}: {e}"}
    sc["slo"] = sl
    _serving_slo_gate(sl, out["extra"]["backend"])
    # compute observatory: achieved FLOP/s + utilization-or-null per loop,
    # compile/recompile accounting, and the steady-state recompile gate —
    # a warm scenario that recompiled after its warm-up refuses to stamp
    out["extra"]["compute"] = _compute_section(out["extra"])
    _compute_gate(out)
    # out-of-core ingest: streaming-parse + GLM-train a dataset larger than
    # the capped host budget, with a spill/fault-in cycle and a streamed-
    # vs-eager bit-identity check (ISSUE 14; docs/INGEST.md) — the gate
    # refuses divergence anywhere and a blown cap on real runs
    try:
        ing = bench_ingest(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        ing = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["ingest"] = ing
    _ingest_gate(ing)
    MEMORY.refresh()
    MEMORY.leak_sweep()
    # compile-cache effectiveness this round (satellite of ROADMAP item 5:
    # the automl wobble is recompiles; the trajectory now records hit rate)
    out["extra"]["compile_cache"] = compile_cache.stats()
    # tracing: overhead measurement + the slowest trace's critical path;
    # gates below refuse to stamp when the span plumbing is broken
    try:
        trc = bench_tracing(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        trc = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["tracing"] = trc
    _tracing_gate(trc)
    # memory: host/device watermarks + DKV byte totals + leak-detector pass
    # over the bench's resident keys; the gate refuses to stamp when the
    # detector fires on a real run (docs/OBSERVABILITY.md "Memory")
    try:
        memsec = bench_memory()
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        memsec = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["memory"] = memsec
    _memory_gate(memsec)
    # ops plane: the health evaluator watching a clean GLM run must stay
    # healthy with zero incidents (hollow-watchdog guard: it must also
    # have actually swept) and under the 2% always-on overhead budget vs
    # H2O3TPU_HEALTH_OFF=1 (ISSUE 15; docs/OBSERVABILITY.md "Health &
    # incidents")
    try:
        hl = bench_health(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        hl = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["health"] = hl
    _health_gate(hl)
    # self-driving ops: replay the chaos classes with remediation in ACT
    # mode — the gate refuses unless every class heals hands-off via one
    # audited action of the right class and the clean run takes none
    # (ISSUE 16; docs/OPERATIONS.md)
    try:
        op = bench_ops(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        op = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["ops"] = op
    _ops_gate(op)
    # flight recorder: always-on sampling must stay under the 2% budget vs
    # H2O3TPU_FLIGHT_OFF=1 (hollow-recorder guard: the thread must tick),
    # the injected RSS trend must open exactly one windowed trend incident,
    # the injected sweep wedge exactly one complete post-mortem, and the
    # clean run neither (ISSUE 17; docs/OBSERVABILITY.md "Flight recorder
    # & post-mortems")
    try:
        flr = bench_flight(ndev)
    except Exception as e:   # noqa: BLE001 — gate reports, then refuses
        flr = {"error": f"{type(e).__name__}: {e}"}
    out["extra"]["flight"] = flr
    _flight_gate(flr)
    # metrics snapshot rides along in the artifact (dispatch counts, parse
    # bytes, model-build latencies) so the perf trajectory carries telemetry;
    # buckets omitted to keep the JSON line compact
    from h2o3_tpu.utils.telemetry import METRICS
    out["extra"]["telemetry"] = METRICS.snapshot(include_buckets=False)
    print(json.dumps(out))
    print(f"# detail: {json.dumps(extra)}", file=sys.stderr)


if __name__ == "__main__":
    main()
