"""ModelBuilder / Model — the algorithm framework.

Reference: ``hex/ModelBuilder.java`` (2,171 LoC: param validation, train/valid
adaptation, Driver lifecycle, n-fold CV orchestration ``computeCrossValidation``
``:608``) and ``hex/Model.java`` (3,482 LoC: ``adaptTestForTrain``,
``score(Frame)`` → BigScore MRTask ``:1866-1959``, metrics hookup).

TPU-first redesign decisions:

- **CV and holdout masking via weights, not sub-frames.** The reference carves
  physical train/holdout frames per fold. Here every algorithm trains against a
  per-row weight vector (0 = excluded), so all folds share one device-resident
  design matrix and every fold's program has identical static shapes — XLA
  compiles once, folds differ only in an input array. (The reference itself
  routes user weights through ``DataInfo._weights``; we promote that to the
  universal mechanism.)
- **Scoring is a jitted batch program**, not a per-row ``score0`` virtual call:
  ``Model._score_raw`` maps the design matrix to predictions on-device.
"""

from __future__ import annotations

import os
import time
import uuid
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.job import Job
from h2o3_tpu.models.metrics import (
    binomial_metrics,
    multinomial_metrics,
    regression_metrics,
)
from h2o3_tpu.ops.map_reduce import map_reduce
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.costs import COSTS
from h2o3_tpu.utils.registry import DKV, LOCKS
from h2o3_tpu.utils.timeline import timed_event


def megastep_k(default: int = 4) -> int:
    """K-step megastep width for device-resident convergence loops
    (``H2O3TPU_MEGASTEP_K``, default 4). The host fetches convergence
    scalars ONCE per K-step megastep instead of once per iteration — with
    JAX async dispatch the K compiled steps pipeline on device and the
    per-step host round-trip disappears from the critical path. Iteration
    counts and results stay exact: the megastep freezes its carry once the
    on-device convergence predicate fires, and the single fetch reconciles
    how many steps actually ran."""
    try:
        k = int(os.environ.get("H2O3TPU_MEGASTEP_K", "") or default)
    except ValueError:
        k = default
    return max(k, 1)


def publish_dispatch_audit(builder, loop: str, iterations: int,
                           host_syncs: int, device_dispatches: int) -> None:
    """Record a convergence loop's host-sync economy: how many blocking
    device→host fetches and compiled dispatches the loop paid for how many
    logical iterations. Feeds ``h2o3_dispatches_per_iteration{loop}`` and
    the builder's ``_dispatch_audit`` (``tests/test_dispatch_audit.py``
    pins the counts)."""
    iters = max(int(iterations), 1)
    audit = getattr(builder, "_dispatch_audit", None)
    if audit is None:
        audit = builder._dispatch_audit = {}
    audit[loop] = dict(iterations=int(iterations),
                       host_syncs=int(host_syncs),
                       device_dispatches=int(device_dispatches),
                       syncs_per_iteration=round(host_syncs / iters, 4))
    _tm.DISPATCHES_PER_ITER.labels(loop=loop).set(host_syncs / iters)


def _weight_rollup(w):
    """Per-shard (rows-with-weight, weight-sum) partial — the classic
    MRTask row count specialized to the padded-weight representation
    (padding and Skip rows carry weight 0, so they never count)."""
    return jnp.sum((w > 0).astype(jnp.float32)), jnp.sum(w)


class ModelParameters(dict):
    """Parameter bag with attribute access and declared defaults.

    Reference: per-algo ``Model.Parameters`` Iced classes with ``@API`` fields;
    here a dict so the REST schema layer can serialize uniformly.
    """

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError:
            raise AttributeError(k) from None

    def __setattr__(self, k, v):
        self[k] = v


class Model:
    """A trained model: artifacts + scoring + metrics (reference: ``hex.Model``)."""

    algo = "model"

    def __init__(self, key: str, params: ModelParameters, data_info: DataInfo | None,
                 response_column: str | None, response_domain: tuple[str, ...] | None,
                 output: dict[str, Any]):
        self.key = key
        # snapshot: the builder's live params dict must not alias into the
        # trained model (builder stays reusable / mutable after train)
        self.params = ModelParameters(params)
        self.data_info = data_info
        self.response_column = response_column
        self.response_domain = response_domain  # None for regression
        self.output = output                    # algo artifacts (device arrays ok)
        self.training_metrics = None
        self.validation_metrics = None
        self.cross_validation_metrics = None
        self.cv_holdout_predictions = None   # [plen] or [plen, K] OOF preds
        self.cv_holdout_mask = None
        # (metric_names, nfolds, rows) for the per-fold summary table
        self.cv_metrics_summary = None
        self.run_time_ms: int = 0
        # per-scoring-event table (reference: Model.Output._scoring_history
        # TwoDimTable, surfaced as h2o-py model.scoring_history()):
        # (columns, rows) where columns = [(name, type, format), ...]
        self.scoring_history: tuple[list, list] | None = None
        # transformers applied to every scoring frame (reference: AutoML
        # bundles the TargetEncoder into the model's scoring pipeline)
        self.preprocessors: list = []

    # -- problem type --------------------------------------------------------

    @property
    def nclasses(self) -> int:
        return len(self.response_domain) if self.response_domain else 0

    @property
    def is_classifier(self) -> bool:
        return self.nclasses >= 2

    # -- scoring -------------------------------------------------------------

    def _score_raw(self, frame: Frame) -> jax.Array:
        """Device predictions: [plen] for regression, [plen, nclasses] probs
        for classification. Implemented per algorithm."""
        raise NotImplementedError

    def _preprocess(self, frame: Frame) -> Frame:
        for p in self.preprocessors:
            if hasattr(p, "is_applied") and p.is_applied(frame):
                continue
            frame = p.transform(frame)
        return frame

    def predict(self, frame: Frame) -> Frame:
        """Score a frame (reference: ``Model.score`` → prediction frame)."""
        frame = self._preprocess(frame)
        raw = self._score_raw(frame)
        n = frame.nrows
        if not self.is_classifier:
            return Frame(["predict"], [Vec.from_device(raw, n, VecType.NUM)])
        labels = decision_labels(self, raw).astype(jnp.int32)
        names = ["predict"] + [f"p{d}" for d in self.response_domain]
        vecs = [Vec.from_device(labels, n, VecType.CAT, domain=self.response_domain)]
        for k in range(self.nclasses):
            vecs.append(Vec.from_device(raw[:, k], n, VecType.NUM))
        return Frame(names, vecs)

    def model_performance(self, frame: Frame):
        """Compute metrics on a (possibly new) frame (reference:
        ``ModelMetrics`` builders run inside BigScore)."""
        if self.response_column not in frame:
            raise ValueError(f"frame lacks response column {self.response_column!r}")
        frame = self._preprocess(frame)
        raw = self._score_raw(frame)
        yvec = frame.vec(self.response_column)
        mask = frame.row_mask()
        from h2o3_tpu.models.data_info import response_adapted
        y, valid = response_adapted(
            yvec, self.response_domain if self.is_classifier else None)
        return compute_metrics(raw, y, mask & valid, self.nclasses)

    # -- persistence hooks ---------------------------------------------------

    def download_mojo(self, path: str) -> str:
        """Export a portable scoring artifact (h2o-py: ``download_mojo``)."""
        from h2o3_tpu.genmodel.mojo import write_mojo
        return write_mojo(self, path)

    def download_pojo(self, path: str) -> str:
        """Export standalone scoring source (h2o-py: ``download_pojo``; here
        a numpy-only Python module instead of a Java class)."""
        from h2o3_tpu.genmodel.codegen import download_pojo
        return download_pojo(self, path)

    def save(self, path: str) -> str:
        """Binary model save (h2o-py: ``h2o.save_model``)."""
        from h2o3_tpu.persist.model_io import save_model
        return save_model(self, path)

    def __repr__(self) -> str:
        lines = [f"{type(self).__name__}(key={self.key!r})"]
        if self.training_metrics:
            lines.append(f"  train: {self.training_metrics!r}")
        if self.validation_metrics:
            lines.append(f"  valid: {self.validation_metrics!r}")
        if self.cross_validation_metrics:
            lines.append(f"  cv:    {self.cross_validation_metrics!r}")
        return "\n".join(lines)


def decision_labels(model, raw):
    """Class labels from raw ``[n, K]`` probabilities — THE one home of the
    reset-able binomial decision threshold (reference:
    ``AstModelResetThreshold`` / ``defaultThreshold``; argmax == 0.5) vs
    argmax choice. Array-agnostic (numpy or jax input, same-kind output):
    ``Model.predict`` and the serving tier's batched finalizer both call
    here, so the two paths cannot drift."""
    thr = getattr(model, "_default_threshold", None)
    if thr is not None and getattr(model, "nclasses", 0) == 2:
        return raw[:, 1] >= float(thr)
    return raw.argmax(axis=1)


def compute_metrics(raw: jax.Array, y: jax.Array, mask: jax.Array, nclasses: int):
    if nclasses == 0:
        return regression_metrics(raw, y, mask)
    if nclasses == 2:
        return binomial_metrics(raw[:, 1], y, mask)
    return multinomial_metrics(raw, y, mask, nclasses)


class ModelBuilder:
    """Algorithm driver base (reference: ``hex.ModelBuilder`` lifecycle:
    validate params → Driver → CV → metrics)."""

    algo = "base"
    supports_classification = True
    supports_regression = True

    def __init__(self, **params):
        self.params = ModelParameters(self.defaults())
        unknown = set(params) - set(self.params) - {"model_id"}
        if unknown:
            raise ValueError(f"{type(self).__name__}: unknown parameters {sorted(unknown)}; "
                             f"valid: {sorted(self.params)}")
        self.params.update(params)
        self.model_id = params.get("model_id")
        self.job: Job | None = None
        self.model: Model | None = None

    # -- subclass contract ---------------------------------------------------

    @classmethod
    def defaults(cls) -> dict:
        return dict(
            seed=-1,
            nfolds=0,
            # Modulo | Random | Stratified (reference hex/FoldAssignment.java)
            fold_assignment="Modulo",
            fold_column=None,           # explicit per-row fold ids
            weights_column=None,
            ignored_columns=None,
            max_runtime_secs=0.0,   # job deadline, enforced in Job.update()
            keep_cross_validation_predictions=False,
            checkpoint=None,     # prior model (key or Model) to resume from
            # auto-checkpoint dir for long builds (reference:
            # -auto_recovery_dir): GBM/XGBoost/DL snapshot a partial model
            # every H2O3TPU_CHECKPOINT_EVERY trees/epochs; a restarted
            # train() with the same dir+params resumes from the snapshot
            # through the checkpoint machinery (docs/RELIABILITY.md)
            auto_recovery_dir=None,
            custom_metric_func=None,   # python callable (preds, y, w) -> value
        )

    def validate_request(self) -> None:
        """Fail-fast validation the REST layer runs BEFORE starting the
        background job: raise ``ValueError`` for a request no build could
        ever satisfy (the server maps it to a structured 400 instead of a
        FAILED job the poller unwraps later). Subclasses extend."""

    def supports_auto_recovery(self) -> bool:
        """True when this builder actually WRITES auto-checkpoint snapshots
        under ``auto_recovery_dir`` (GBM/XGBoost-gbtree chunk snapshots, DL
        epoch snapshots). Base builders don't — advertising
        ``auto_recoverable`` for them would promise a resume that silently
        restarts from scratch."""
        return False

    def _resolve_checkpoint(self) -> "Model | None":
        """Resolve the ``checkpoint`` param to a prior Model (reference:
        ``Model.Parameters._checkpoint``, trees ``SharedTree.java:144,241``,
        DL ``DeepLearning.java:348``)."""
        cp = self.params.get("checkpoint")
        if cp is None:
            return None
        if isinstance(cp, Model):
            self.params["checkpoint"] = cp.key   # don't drag the model object
            return cp                            # into param snapshots/pickles
        model = DKV.get(cp)
        if model is None:
            raise ValueError(f"checkpoint model {cp!r} not found in DKV")
        if model.algo != self.algo:
            raise ValueError(f"checkpoint is a {model.algo!r} model; "
                             f"this builder is {self.algo!r}")
        return model

    def _fit(self, job: Job, frame: Frame, x: list[str], y: str | None,
             weights: jax.Array) -> Model:
        """Train on rows where weights>0; must honor job.update/cancel."""
        raise NotImplementedError

    def _apply_custom_metric(self, model: Model, frame: Frame, y: str,
                             weights, fn, mm=None) -> None:
        """Evaluate a user metric callable on predictions over ``frame`` and
        attach it to ``mm`` (default: training metrics). Reference:
        custom_metric_func via water/udf — computed for every scored frame
        (CMetricScoringTask), so validation metrics carry it too."""
        import numpy as np

        from h2o3_tpu.models.data_info import response_adapted
        from h2o3_tpu.parallel.distributed import fetch
        raw = fetch(model._score_raw(frame))[: frame.nrows]
        yv, valid = response_adapted(
            frame.vec(y),
            model.response_domain if model.is_classifier else None)
        ok = fetch(frame.row_mask() & valid)[: frame.nrows]
        w = fetch(weights)[: frame.nrows] * ok if weights is not None else ok
        value = fn(np.asarray(raw), fetch(yv)[: frame.nrows], np.asarray(w))
        mm = model.training_metrics if mm is None else mm
        try:
            mm.custom_metric_name = getattr(fn, "__name__", "custom")
            mm.custom_metric_value = float(value)
        except AttributeError:   # frozen dataclass
            object.__setattr__(mm, "custom_metric_name",
                               getattr(fn, "__name__", "custom"))
            object.__setattr__(mm, "custom_metric_value", float(value))

    # -- public train API (mirrors h2o-py estimator.train) -------------------

    def train(self, x: Sequence[str] | None = None, y: str | None = None,
              training_frame: Frame | None = None, validation_frame: Frame | None = None,
              weights: jax.Array | None = None) -> Model:
        # one phase for the whole build, so that what it does before
        # `<algo>:fit` and after `<algo>:metrics` (frame adaptation, roll-ups
        # asked from here, Job, DKV put) has a name where first calls are
        # booked (utils/compile_cache.py); kind "phase": no memory sample
        with timed_event("phase", f"{self.algo}:train"):
            return self._train(x, y, training_frame, validation_frame,
                               weights)

    def _train(self, x, y, training_frame, validation_frame,
               weights) -> Model:
        frame = training_frame
        if frame is None:
            raise ValueError("training_frame is required")
        if y is None and not getattr(self, "unsupervised", False):
            raise ValueError(f"{self.algo} is supervised: y is required")
        # slice-bound build (orchestration/scheduler.py lease): reshard the
        # inputs onto the bound mesh ONCE, up front — every downstream mesh
        # (row_sharding, map_reduce, tree.hist_mesh from input shardings)
        # then resolves inside the slice, so a build compiled on slice 0
        # never embeds slice 1's devices and concurrent builds never share
        # a collective rendezvous
        from h2o3_tpu.parallel import mesh as _pmesh
        bound = _pmesh.bound_mesh()
        # user-facing name for Job/extension surfaces: the reshard below
        # swaps in an internal `{key}::mesh[...]` view key that means
        # nothing to the user (and may be evicted before they look)
        user_frame_key = frame.key
        if bound is not None:
            frame = frame.on_mesh(bound)
            if validation_frame is not None:
                validation_frame = validation_frame.on_mesh(bound)
            if weights is not None and isinstance(weights, jax.Array):
                from jax.sharding import NamedSharding, PartitionSpec as _P
                weights = jax.device_put(
                    weights, NamedSharding(bound, _P(_pmesh.ROWS)))
            from h2o3_tpu.utils.tracing import TRACER as _trc
            _trc.mark_active(mesh_devices=",".join(
                str(i) for i in _pmesh.mesh_device_ids(bound)))
        ignored = set(self.params.get("ignored_columns") or [])
        if self.params.get("weights_column"):
            ignored.add(self.params["weights_column"])
        if self.params.get("offset_column"):
            ignored.add(self.params["offset_column"])
        if self.params.get("fold_column"):
            ignored.add(self.params["fold_column"])
        x = [c for c in (x if x is not None else frame.names)
             if c != y and c not in ignored and frame.vec(c).type.on_device]
        if not x:
            raise ValueError("no usable feature columns")
        self._validate(frame, x, y)

        base_w = frame.row_mask().astype(jnp.float32)
        if self.params.get("weights_column"):
            base_w = base_w * frame.vec(self.params["weights_column"]).data
        if weights is not None:
            base_w = base_w * weights

        # stashed for trainers that score held-out data mid-train (GBM/DRF
        # early stopping on the validation frame, ScoreKeeper semantics)
        self._validation_frame = validation_frame
        self._x_cols = x
        self._y_col = y

        # auto-recovery: when a prior run with this dir+params left a
        # partial-model snapshot, resume through the ordinary checkpoint
        # machinery (seed-derived per-tree keys make the resumed GBM
        # bit-identical to an uninterrupted run); an explicit checkpoint=
        # from the caller wins over the snapshot
        self._build_recovery = None
        self._resume_snap_key = None
        rdir = self.params.get("auto_recovery_dir")
        if rdir and not self.supports_auto_recovery():
            # no snapshot will ever be written: keep the job's
            # auto_recoverable contract honest rather than advertising a
            # resume that would restart from scratch
            rdir = None
        if rdir:
            from h2o3_tpu.persist.recovery import BuildRecovery
            self._build_recovery = BuildRecovery(str(rdir))
            if not self.params.get("checkpoint"):
                snap = self._build_recovery.load_snapshot(self.params)
                if snap is not None:
                    # load_model already re-registered it in the DKV (so
                    # every checkpoint consumer — CV refits resolve by key —
                    # can see it); remember the key to remove after the run
                    self._resume_snap_key = snap.key
                    self.params["checkpoint"] = snap

        self.job = Job(f"{self.algo} on {user_frame_key or 'frame'}",
                       max_runtime_secs=float(
                           self.params.get("max_runtime_secs") or 0.0))
        self.job.auto_recovery_dir = rdir
        if getattr(self, "_cancel_requested_early", False):
            # a REST cancel raced job creation (see server._run_build_job):
            # honor it now, before the build starts
            self.job.cancel()
        t0 = time.time()

        self._score_series = None   # per-train metric series (tree builders)

        def driver(job: Job) -> Model:
            from h2o3_tpu.utils import extensions as _ext
            # Lockable protocol (water/Lockable.java): the build holds the
            # write lock on its (named) destination key from first fit to
            # final DKV.put, so a concurrent DELETE waits and a mid-build
            # delete cannot be resurrected by the final put.  Anonymous
            # (auto-generated) keys are unguessable, so a None model_id
            # needs no lock.  Covers every build path: direct, REST, grid,
            # AutoML (reentrant for the REST path, which already holds it).
            with LOCKS.write(self.model_id):
                return locked_driver(job, _ext)

        def locked_driver(job: Job, _ext) -> Model:
            _ext.report("model_build_start", algo=self.algo, job=job.key,
                        frame=user_frame_key)
            # build wall-time lands in the timeline ring (kind "model") and
            # in the metrics registry; scoring history carries it through
            # run_time_ms (reference: TwoDimTable duration column)
            # the fit runs under a CostMeter site scope so persistent
            # compile-cache hits/misses during the build credit this algo
            # (utils/compile_cache.py by_site — docs/OBSERVABILITY.md
            # "Compute")
            with timed_event("model", f"{self.algo}:fit"), \
                    COSTS.scope(f"fit:{self.algo}"):
                model = self._fit(job, frame, x, y, base_w)
                # effective-rows rollup through the EXPLICIT MRTask path
                # (reference: every build's GLMIterationTask-style row
                # count): one tiny psum per build keeps partition dispatch —
                # and its per-shard straggler attribution — in every model's
                # trace subtree, and nobs/weight-sum land in the output.
                # Runs AFTER fit over the weights the fit actually used
                # (GLM Skip zeroes NA-row weights into _metrics_weights)
                w_eff = getattr(self, "_metrics_weights", None)
                if w_eff is None:
                    w_eff = base_w
                nobs_d, wsum_d = map_reduce(_weight_rollup, w_eff)
                nobs, wsum = (float(v) for v in
                              jax.device_get((nobs_d, wsum_d)))
                model.output.setdefault("effective_nobs", int(nobs))
                model.output.setdefault("weight_sum", wsum)
            # a builder may shrink the effective row set during fit (GLM
            # missing_values_handling=Skip zeroes NA-row weights); metrics
            # and CV must see the same rows the fit saw (reference: Skip
            # rows carry weight 0 everywhere)
            w_metrics = getattr(self, "_metrics_weights", None)
            if w_metrics is None:
                w_metrics = base_w
            model.run_time_ms = int((time.time() - t0) * 1000)
            _tm.MODEL_BUILDS.labels(algo=self.algo).inc()
            _tm.MODEL_BUILD_SECONDS.labels(algo=self.algo).observe(
                model.run_time_ms / 1000.0)
            # user UDF metric: either an in-process python callable
            # (preds, y, w) -> value, or the reference's wire form
            # "python:key=module.Class" naming a /3/PutKey upload
            # (water/udf CFuncRef; h2o.upload_custom_metric). Resolved
            # up-front so validation scoring sees the callable even when
            # training metrics are absent (CMetricScoringTask computes the
            # custom metric on EVERY scored frame).
            cmf = self.params.get("custom_metric_func")
            if isinstance(cmf, str) and y is not None:
                from h2o3_tpu.utils import udf as _udf
                _, key_name, _qual = _udf.parse_ref(cmf)
                cmf = _udf.metric_callable(_udf.load_cfunc(cmf), key_name,
                                           model=model)
            if y is not None:
                with timed_event("phase", f"{self.algo}:metrics"):
                    model.training_metrics = self._holdout_metrics(
                        model, frame, y, w_metrics)
                if cmf is not None and model.training_metrics is not None:
                    self._apply_custom_metric(model, frame, y, w_metrics, cmf)
            if validation_frame is not None and y is not None:
                model.validation_metrics = model.model_performance(validation_frame)
                if cmf is not None and model.validation_metrics is not None:
                    # weights apply on every scored frame, validation included
                    vw = None
                    wc = self.params.get("weights_column")
                    if wc and wc in validation_frame.names:
                        vw = (validation_frame.row_mask().astype(jnp.float32)
                              * validation_frame.vec(wc).data)
                    self._apply_custom_metric(model, validation_frame, y,
                                              vw, cmf,
                                              mm=model.validation_metrics)
            # snapshot BEFORE the CV refits below clobber the per-iteration
            # series on this (shared) builder instance
            model.scoring_history = self._scoring_history(model)
            nfolds = int(self.params.get("nfolds") or 0)
            if self.params.get("fold_column"):
                # an explicit fold column defines the folds outright
                # (reference: ModelBuilder.init rejects combining it with
                # nfolds and requires >= 2 distinct fold values)
                if nfolds:
                    raise ValueError(
                        "specify either fold_column or nfolds, not both")
                nfolds = self._fold_column_cardinality(frame)
                if nfolds < 2:
                    raise ValueError(
                        f"fold_column {self.params['fold_column']!r} must "
                        "hold at least 2 distinct folds")
            if nfolds >= 2 and y is not None:
                model.cross_validation_metrics = self._cross_validate(
                    job, frame, x, y, w_metrics, nfolds, model)
            # artifact size (summed bytes of the model's array tree —
            # coefficients / tree arrays / DL weights) rides in the output
            # and is what /3/Memory reports for the model's DKV key
            from h2o3_tpu.utils.memory import array_tree_bytes
            model.output.setdefault("artifact_bytes",
                                    array_tree_bytes(model))
            DKV.put(model.key, model)
            _ext.report("model_build_end", algo=self.algo, model=model.key,
                        job=job.key)
            return model

        self.model = self.job.run(driver)
        if bound is not None and _pmesh.rehome_requested() \
                and self.job.result is not None:
            # the model's artifacts (coefficients, tree heaps, OOF
            # predictions) are committed to the slice's devices; re-home
            # them onto the scheduler's base mesh so downstream consumers
            # (predict on base-mesh frames, stacked-ensemble level-one
            # assembly across models built on DIFFERENT slices) never mix
            # device sets in one program — XLA raises on incompatible
            # devices
            _pmesh.rehome(self.job.result, _pmesh.rehome_target())
        if self._resume_snap_key:
            # the transient resume-source model has served its purpose
            DKV.remove(self._resume_snap_key)
        if self.job.status == Job.FAILED:
            raise self.job.exception
        if self.job.status == Job.CANCELLED and self.job.result is None:
            # the build stopped (explicit cancel or max_runtime_secs) before
            # it could produce even a partial model — surfacing None would
            # read as success; builders that keep partial results (GBM's
            # built trees) return them with the job still marked CANCELLED
            from h2o3_tpu.models.job import JobCancelled
            raise JobCancelled(
                f"{self.algo} build cancelled"
                + (" (max_runtime_secs exceeded)"
                   if self.job.deadline_exceeded else ""))
        if self.job.status == Job.DONE and self._build_recovery is not None:
            # only a COMPLETED build retires its snapshot: a deadline-
            # cancelled partial keeps it so a rerun resumes where it stopped
            self._build_recovery.complete()
        return self.job.result

    def train_segments(self, segments: list[str], y: str,
                       training_frame: Frame, x: list[str] | None = None,
                       segment_models_id: str | None = None):
        """Train one model per unique segment combo (h2o-py
        ``estimator.train_segments``; reference hex/segments)."""
        from h2o3_tpu.orchestration.segments import train_segments
        return train_segments(self, segments, training_frame, y, x=x,
                              segment_models_id=segment_models_id)

    # -- helpers -------------------------------------------------------------

    def _scoring_history(self, model: Model):
        """Per-scoring-event table hook (reference: ``SharedTree.java:798``
        ``doScoringAndSaveModel`` fills a TwoDimTable per iteration).
        Iterative builders override; returns (columns, rows) or None."""
        return None

    def _history_table(self, model: Model, value_cols, values):
        """Shared timestamp/duration scaffold for scoring-history rows:
        ``value_cols`` = [(name, type, format), ...], ``values`` = one value
        list per scoring event (duration is interpolated from the total
        train wall-clock — the events happened inside one fused program)."""
        if not values:
            return None
        stamp = time.strftime("%Y-%m-%d %H:%M:%S")
        total_s = model.run_time_ms / 1000.0
        n = len(values)
        cols = [("timestamp", "string", "%s"),
                ("duration", "string", "%s")] + list(value_cols)
        rows = [[stamp, f"{total_s * (i + 1) / n:.3f} sec", *vals]
                for i, vals in enumerate(values)]
        return cols, rows

    def _validate(self, frame: Frame, x: list[str], y: str | None) -> None:
        if y is not None:
            yv = frame.vec(y)
            if yv.is_categorical and not self.supports_classification:
                raise ValueError(f"{self.algo} does not support a categorical response")
            if not yv.is_categorical and not self.supports_regression:
                raise ValueError(f"{self.algo} requires a categorical response")

    def _holdout_metrics(self, model: Model, frame: Frame, y: str, w: jax.Array):
        from h2o3_tpu.models.data_info import response_as_float
        # a fit that already produced training-row predictions (e.g. the
        # boosting scan's final margins) caches them on the transient builder
        # — skip the full re-score of the training frame
        raw = getattr(self, "_last_train_raw", None)
        self._last_train_raw = None
        if raw is None:
            raw = model._score_raw(frame)
        yy, valid = response_as_float(frame.vec(y))
        return compute_metrics(raw, yy, (w > 0) & valid, model.nclasses)

    def _fold_column_values(self, frame: Frame) -> np.ndarray:
        """Per-row fold codes from the explicit fold column: distinct
        values map to 0..K-1 in sorted order (reference:
        ``FoldAssignment.fromUserFoldSpecification``).  NA fold values are
        rejected like the reference does — a silent default would leak
        those rows into every fold's training set.  Cached per frame:
        train() needs it for the cardinality and _cross_validate for the
        ids — one host pass, not two."""
        cache = getattr(self, "_fold_values_cache", None)
        if cache is not None and cache[0] is frame:
            return cache[1]
        v = frame.vec(self.params["fold_column"])
        vals = np.asarray(v.data)[: frame.plen].astype(np.float64)
        body = vals[: frame.nrows]
        na = (body < 0) if v.type is VecType.CAT else np.isnan(body)
        if na.any():
            raise ValueError(
                f"fold_column {self.params['fold_column']!r} has "
                f"{int(na.sum())} missing values; every row needs a fold")
        uniq = np.unique(body)
        # padding rows map to fold 0; they carry weight 0 everywhere
        safe = np.where(np.isnan(vals) | (vals < uniq[0]), uniq[0], vals)
        out = np.searchsorted(uniq, safe).clip(0, len(uniq) - 1) \
            .astype(np.int32)
        self._fold_values_cache = (frame, out)
        return out

    def _fold_column_cardinality(self, frame: Frame) -> int:
        return int(self._fold_column_values(frame).max()) + 1

    def _fold_ids(self, frame: Frame, nfolds: int, yvec=None) -> jax.Array:
        """Fold assignment vector (reference: ``hex/FoldAssignment.java``):
        Modulo (default), Random, Stratified (per-class round-robin so
        every fold sees every response class), or an explicit fold
        column."""
        plen = frame.plen
        if self.params.get("fold_column"):
            return jnp.asarray(self._fold_column_values(frame))
        assignment = self.params.get("fold_assignment", "Modulo")
        if assignment == "Random":
            seed = int(self.params.get("seed") or -1)
            key = jax.random.PRNGKey(seed if seed >= 0 else 907)
            return jax.random.randint(key, (plen,), 0, nfolds)
        if assignment == "Stratified":
            if yvec is None or not yvec.is_categorical:
                # reference FoldAssignment: stratification needs a
                # categorical response — refuse rather than silently
                # degrade to Modulo
                raise ValueError("fold_assignment='Stratified' requires a "
                                 "categorical response")
            codes = np.asarray(yvec.data)[:plen]
            ids = np.arange(plen, dtype=np.int32) % nfolds
            for c in np.unique(codes[codes >= 0]):
                rows = np.where(codes == c)[0]
                ids[rows] = np.arange(len(rows)) % nfolds
            return jnp.asarray(ids)
        return jnp.arange(plen) % nfolds

    def _cross_validate(self, job: Job, frame: Frame, x: list[str], y: str,
                        base_w: jax.Array, nfolds: int, model: Model | None = None):
        """K-fold CV: same compiled program per fold, weights differ
        (reference: ``ModelBuilder.computeCrossValidation`` builds physical
        sub-frames; see module docstring for why masking replaces that)."""
        from h2o3_tpu.models.data_info import response_as_float
        yvec = frame.vec(y)
        folds = self._fold_ids(frame, nfolds, yvec)
        yy, valid = response_as_float(yvec)
        raws, masks = [], []
        for k in range(nfolds):
            w_train = base_w * (folds != k)
            cv_builder = type(self)(**{**self.params, "nfolds": 0})
            cv_model = cv_builder._fit(job, frame, x, y, w_train)
            raw_k = cv_model._score_raw(frame)
            hold = (base_w > 0) & (folds == k) & valid
            raws.append(raw_k)
            masks.append(hold)
        # pool holdout predictions into one metrics pass (reference: CV main
        # metrics are computed on merged holdout predictions)
        nclass = len(yvec.domain) if yvec.is_categorical else 0
        pooled = sum(jnp.where((m[:, None] if r.ndim == 2 else m), r, 0.0)
                     for r, m in zip(raws, masks))
        any_mask = jnp.stack(masks).any(axis=0)
        if model is not None and self.params.get("keep_cross_validation_predictions"):
            # out-of-fold predictions feed the StackedEnsemble metalearner
            # (reference: keep_cross_validation_predictions + holdout frames)
            model.cv_holdout_predictions = pooled
            model.cv_holdout_mask = any_mask
        if model is not None:
            # per-fold metric table (reference: ModelBuilder
            # cross_validation_metrics_summary TwoDimTable — mean/sd +
            # one column per fold; h2o-py's
            # model.cross_validation_metrics_summary() reads it)
            per_fold = [compute_metrics(r, yy, m, nclass)
                        for r, m in zip(raws, masks)]
            names = [f for f in ("mse", "rmse", "logloss", "auc", "pr_auc",
                                 "mae", "r2", "mean_per_class_error")
                     if getattr(per_fold[0], f, None) is not None]
            rows = []
            for f in names:
                vals = np.array([float(getattr(pf, f)) for pf in per_fold])
                # an empty-holdout fold (all rows zero-weight / NA
                # response) yields NaN metrics; mean/sd summarize the
                # FINITE folds so one bad fold can't blank the table
                fin = vals[np.isfinite(vals)]
                mean = float(fin.mean()) if fin.size else float("nan")
                sd = float(fin.std(ddof=1)) if fin.size > 1 else 0.0
                rows.append([f, mean, sd] + [float(v) for v in vals])
            model.cv_metrics_summary = (names, nfolds, rows)
        return compute_metrics(pooled, yy, any_mask, nclass)


def make_model_key(algo: str, model_id: str | None) -> str:
    return model_id or f"{algo}_{uuid.uuid4().hex[:10]}"
