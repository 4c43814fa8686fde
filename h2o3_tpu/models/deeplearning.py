"""DeepLearning — feed-forward MLP (classification / regression / autoencoder).

Reference: ``hex/deeplearning/`` (5.8 kLoC). The reference trains with
**Hogwild! lock-free intra-node SGD + per-iteration cross-node model
averaging** (``hex/deeplearning/DeepLearningTask.java:17-90``,
``DeepLearning.java:379-478``): threads race on shared per-node weights,
then nodes average. Forward/backward math, ADADELTA, momentum ramp, dropout
and maxout live in ``hex/deeplearning/Neurons.java`` (``bpropMiniBatch:135``).

TPU-first redesign (SURVEY.md §7 step 7): Hogwild is a CPU-cache trick with
no accelerator analog — the same statistical contract (stochastic minibatch
updates whose gradient is averaged across the cluster each step) is expressed
as **synchronous data-parallel minibatch SGD**: the design matrix is
row-sharded across the mesh, each step consumes one shuffled minibatch, XLA
inserts the gradient all-reduce over ICI (replacing per-iteration model
averaging with per-step exact averaging — strictly less stale). The whole
epoch is one jitted ``lax.scan`` over minibatches: zero host round-trips in
the hot loop, weights live in HBM, matmuls hit the MXU in bf16-friendly f32.

Supported reference options: activations Tanh/Rectifier/Maxout (+WithDropout),
``adaptive_rate`` ADADELTA(rho, epsilon) or annealed-rate momentum SGD with
Nesterov, ``input_dropout_ratio``/``hidden_dropout_ratios`` (inverted dropout),
``l1``/``l2``, ``max_w2`` per-unit norm constraint, loss CrossEntropy/
Quadratic/Absolute/Huber, ``initial_weight_distribution`` UniformAdaptive/
Uniform/Normal, ``autoencoder`` with reconstruction-error anomaly scoring
(reference ``DlInput``/``Neurons`` semantics).

Three reference parameters whose meaning is about the reference's
MapReduce iteration, and what they mean in this loop:

- ``train_samples_per_iteration`` (-2): the reference trains that many rows
  between two model averagings (-2 auto-tuned, -1 every node's rows, 0 one
  epoch). Here every update already averages the gradient over the whole
  cluster exactly, so no value can change the weights: -2, -1 and 0 all mean
  what the loop does, a dispatch of whole epochs. A positive value is
  accepted with the same effect on the weights (none) and is logged once as
  setting nothing: the loop reports and checkpoints at epoch boundaries.
  Values under -2 are refused, as in the reference.
- ``classification_stop`` (0): the reference stops once the training error it
  scores between iterations is at or under the value; -1 never stops. This
  loop does not score between epochs (one fetch a build), so a value >= 0 is
  NOT honoured: it trains the ``epochs`` asked for, as it always has, and
  says so in the log once. -1 is exactly what the loop does.
- ``ignore_const_cols`` (true): predictors constant over the training rows
  are left out of the network's inputs (``DataInfo.make``); the model's
  ``data_info.ignored_const_cols`` names them and scoring frames may keep them.

``epochs`` may be fractional, as in the reference: whole epochs are
``rows // mini_batch_size`` updates each, and the last, partial one is
``ceil(fraction x rows / mini_batch_size)`` updates on the first rows of a
fresh permutation (``rows`` the frame's padded length, as every epoch's).
The elastic mode rounds ``epochs`` up to whole epochs.
"""

from __future__ import annotations

import logging
import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.types import VecType
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models.data_info import DataInfo, response_as_float
from h2o3_tpu.models.job import Job
from h2o3_tpu.models.model_base import (Model, ModelBuilder, ModelParameters,
                                        make_model_key, megastep_k,
                                        publish_dispatch_audit)
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils.costs import accounted_jit
from h2o3_tpu.utils.timeline import timed_event


# ---------------------------------------------------------------------------
# forward / backward
# ---------------------------------------------------------------------------

def _act_kind(activation: str) -> tuple[str, bool]:
    """Map reference activation enum → (base activation, hidden dropout on)."""
    a = activation.lower()
    drop = a.endswith("withdropout")
    base = a.replace("withdropout", "")
    if base not in ("tanh", "rectifier", "maxout"):
        raise ValueError(f"unknown activation {activation!r}")
    return base, drop


#: what every product of this module states. DEFAULT on float32 operands is,
#: on a TPU, ONE pass of the MXU over operands rounded to bfloat16 with the
#: sums kept in float32 (read on the v5e, PERF.md section 6, PR 32); on the
#: CPU it is a float32 product. Parameters, gradients and optimiser state are
#: float32 everywhere. Written out so that a changed ``jax_default_matmul_
#: precision`` cannot move a build; raise it here, never lower it.
_PRECISION = jax.lax.Precision.DEFAULT


def _dense(h, W, b):
    return jnp.dot(h, W, precision=_PRECISION,
                   preferred_element_type=jnp.float32) + b


def _layer_widths(params, act: str) -> list[int]:
    """[inputs, units of hidden layer 0, ...] from the weights' shapes."""
    ws = params["W"]
    return [ws[0].shape[0]] + [W.shape[1] // (2 if act == "maxout" else 1)
                               for W in ws[:-1]]


def _dropout_masks(key, rows: int, widths, in_drop: float,
                   hid_drops: tuple[float, ...]) -> list:
    """The keep-masks of one minibatch: ``[rows, widths[0]]`` for the input,
    ``[rows, widths[i + 1]]`` after hidden layer ``i`` (``_layer_widths``);
    None where the ratio is 0. One split of ``key`` a mask, in layer order:
    the order the forward pass has always drawn them in, so a seed gives the
    bits it gave. The random stream's one home: a plain reference that is
    handed the masks as arrays (benchmark/reference/dl_mlp_jnp.py) draws
    them here."""
    ratios = [in_drop] + [hid_drops[i] if i < len(hid_drops) else 0.0
                          for i in range(len(widths) - 1)]
    masks = []
    for width, p in zip(widths, ratios):
        if p > 0:
            key, sub = jax.random.split(key)
            masks.append(jax.random.bernoulli(sub, 1.0 - p, (rows, width)))
        else:
            masks.append(None)
    return masks


def _drop(h, keep, p: float):
    """Inverted dropout: the kept units are scaled at training time, so
    scoring needs no rescale."""
    return h if keep is None else jnp.where(keep, h / (1.0 - p), 0.0)


def _forward(params, X, act: str, masks=None, in_drop: float = 0.0,
             hid_drops: tuple[float, ...] = ()):
    """MLP forward pass. Maxout layers hold W of width 2*units and take the
    pairwise max (reference: 2-channel Maxout, ``Neurons.java``). ``masks``
    (``_dropout_masks``) is given at training time only."""
    n_hidden = len(params["W"]) - 1
    if masks is None:
        masks = [None] * (n_hidden + 1)
    with jax.named_scope("dropout"):
        h = _drop(X, masks[0], in_drop)
    for i in range(n_hidden):
        with jax.named_scope("forward"):
            z = _dense(h, params["W"][i], params["b"][i])
            if act == "tanh":
                h = jnp.tanh(z)
            elif act == "rectifier":
                h = jnp.maximum(z, 0.0)
            else:  # maxout: [B, 2u] → max over channel pairs → [B, u]
                u = z.shape[-1] // 2
                h = jnp.maximum(z[..., :u], z[..., u:])
        with jax.named_scope("dropout"):
            h = _drop(h, masks[i + 1],
                      hid_drops[i] if i < len(hid_drops) else 0.0)
    with jax.named_scope("forward"):
        # linear output (logits / preds)
        return _dense(h, params["W"][-1], params["b"][-1])


def _row_loss(out, y, w, loss: str, nclasses: int, huber_delta: float):
    """Weighted per-row loss summed over the batch (reference loss enum)."""
    if nclasses >= 2:
        logp = jax.nn.log_softmax(out, axis=-1)
        yi = y.astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, yi[:, None], axis=1)[:, 0]
        return (w * nll).sum()
    err = out - (y if out.ndim == 1 else y.reshape(out.shape))
    if loss == "absolute":
        e = jnp.abs(err)
    elif loss == "huber":
        a = jnp.abs(err)
        e = jnp.where(a <= huber_delta, 0.5 * a * a,
                      huber_delta * (a - 0.5 * huber_delta))
    else:  # quadratic
        e = 0.5 * err * err
    if e.ndim == 2:            # autoencoder / multi-output: sum over outputs
        e = e.sum(axis=1)
    return (w * e).sum()


# ---------------------------------------------------------------------------
# one jitted training "iteration": scan over minibatches
# ---------------------------------------------------------------------------

def _epoch_steps(params, opt, Xb, yb, wb, key, samples0,
                 act: str, loss: str, nclasses: int, cfg: tuple):
    """Scan all minibatches of one (shuffled) epoch — the traceable body
    the K-epoch megastep scan runs per epoch.

    Xb: [nb, B, K] minibatched design matrix, yb: [nb, B], wb: [nb, B].
    cfg is a hashable tuple of hyperparameters (see _fit for layout).
    """
    (adaptive, rho, eps, rate, rate_annealing, rate_decay,
     mom_start, mom_ramp, mom_stable, nesterov,
     l1, l2, max_w2, in_drop, hid_drops, huber_delta) = cfg

    widths = _layer_widths(params, act)

    def grad_fn(p, X, y, w, masks):
        out = _forward(p, X, act, masks, in_drop, hid_drops)
        with jax.named_scope("loss"):
            if nclasses == 0 and out.shape[-1] == 1 and y.ndim == 1:
                out = out[:, 0]
            lsum = _row_loss(out, y, w, loss, nclasses, huber_delta)
            return lsum / jnp.maximum(w.sum(), 1e-8)

    def apply_l1l2(g, p):
        return jax.tree.map(lambda gi, pi: gi + l2 * pi + l1 * jnp.sign(pi), g, p)

    def constrain(p):
        # reference default max_w2 = Float.MAX_VALUE means "disabled"; values
        # that big also overflow bf16/f32 intermediates on TPU, so gate here
        if max_w2 <= 0 or not np.isfinite(max_w2) or max_w2 >= 1e30:
            return p
        # per-unit incoming squared-norm cap (reference Neurons max_w2)
        def cap(W):
            if W.ndim != 2:
                return W
            ss = (W * W).sum(axis=0, keepdims=True)
            return W * jnp.sqrt(max_w2 / jnp.maximum(ss, max_w2))
        return {"W": [cap(W) for W in p["W"]], "b": p["b"]}

    def update(p, o, g, samples):
        if adaptive:
            # ADADELTA (reference Neurons.java adaDelta branch)
            Eg = jax.tree.map(lambda e, gi: rho * e + (1 - rho) * gi * gi, o["Eg"], g)
            dx = jax.tree.map(
                lambda ed, eg, gi: -jnp.sqrt(ed + eps) / jnp.sqrt(eg + eps) * gi,
                o["Edx"], Eg, g)
            Edx = jax.tree.map(lambda e, d: rho * e + (1 - rho) * d * d, o["Edx"], dx)
            p = jax.tree.map(jnp.add, p, dx)
            o = {"Eg": Eg, "Edx": Edx, "v": o["v"]}
        else:
            lr0 = rate / (1.0 + rate_annealing * samples)
            # per-layer rate decay (reference DeepLearningParameters.rate_decay:
            # layer i trains at rate * rate_decay^i)
            lrs = [lr0 * (rate_decay ** i) for i in range(len(p["W"]))]
            mom = jnp.where(
                mom_ramp > 0,
                jnp.minimum(mom_stable,
                            mom_start + samples * (mom_stable - mom_start)
                            / jnp.maximum(mom_ramp, 1.0)),
                mom_stable)
            v = {kk: [mom * vi - lrs[i] * gi
                      for i, (vi, gi) in enumerate(zip(o["v"][kk], g[kk]))]
                 for kk in ("W", "b")}
            if nesterov:
                p = {kk: [pi + mom * vi - lrs[i] * gi
                          for i, (pi, vi, gi) in enumerate(zip(p[kk], v[kk], g[kk]))]
                     for kk in ("W", "b")}
            else:
                p = jax.tree.map(jnp.add, p, v)
            o = {"Eg": o["Eg"], "Edx": o["Edx"], "v": v}
        return p, o

    def step(carry, xs):
        p, o, k, samples = carry
        X, y, w = xs
        k, sub = jax.random.split(k)
        with jax.named_scope("dropout"):
            masks = _dropout_masks(sub, X.shape[0], widths, in_drop, hid_drops)
        lossv, g = jax.value_and_grad(grad_fn)(p, X, y, w, masks)
        with jax.named_scope("regularize"):
            g = apply_l1l2(g, p)
        with jax.named_scope("optimizer"):
            p, o = update(p, o, g, samples)
        with jax.named_scope("constrain"):
            p = constrain(p)
        samples = samples + w.sum()
        return (p, o, k, samples), lossv

    (params, opt, key, samples), losses = jax.lax.scan(
        step, (params, opt, key, samples0), (Xb, yb, wb))
    return params, opt, key, samples, losses.mean()


def _epoch_keys(key):
    """(the next epoch's key, the key of this epoch's permutation, the key
    its updates split their dropout keys from): the stream's order, kept in
    one place for the program and for whoever replays it."""
    key, pk = jax.random.split(key)
    key, ek = jax.random.split(key)
    return key, pk, ek


def _epoch_plan(epochs: float, rows: int, B: int) -> tuple[int, int, int]:
    """(updates a whole epoch, whole epochs, updates of a last partial epoch
    or 0) for ``epochs`` passes over ``rows`` rows in minibatches of ``B``.
    Training stops after ``epochs x rows`` samples, as the reference's does:
    the fraction left after the whole epochs is rounded up to whole updates.
    Nothing to train (``epochs`` <= 0) is one epoch, as it always was."""
    nb = rows // B
    whole = max(int(math.floor(epochs)), 0)
    last = min(nb, math.ceil((epochs - whole) * rows / B)) if epochs > 0 else 0
    if not whole and not last:
        whole = 1
    return nb, whole, last


@accounted_jit("dl:train_epochs", loop="dl_epoch",
               static_argnames=("act", "loss", "nclasses", "cfg", "k",
                                "nb", "B", "autoenc"))
def _train_epochs(params, opt, X, yy, w, key, samples0,
                  act: str, loss: str, nclasses: int, cfg: tuple, k: int,
                  nb: int, B: int, autoenc: bool):
    """K whole epochs in ONE compiled dispatch: shuffle → minibatch →
    step-scan all run on device, so consecutive epochs pipeline with zero
    host dispatches between them (the K-step megastep of the DL loop).

    The PRNG stream is split in exactly the order the per-epoch host loop
    used (``key → pk`` for the permutation, then ``key → ek`` for the
    in-epoch dropout/minibatch stream), so K-epoch training is
    reproducibility-identical to K single-epoch dispatches."""
    used = nb * B
    K = X.shape[1]

    def epoch(carry, _):
        params, opt, key, samples = carry
        key, pk, ek = _epoch_keys(key)
        with jax.named_scope("shuffle"):
            perm = jax.random.permutation(pk, X.shape[0])[:used]
            Xb = jnp.take(X, perm, axis=0).reshape(nb, B, K)
            wb = jnp.take(w, perm, axis=0).reshape(nb, B)
            ybt = Xb if autoenc else jnp.take(yy, perm, axis=0).reshape(nb, B)
        params, opt, _, samples, mloss = _epoch_steps(
            params, opt, Xb, ybt, wb, ek, samples, act, loss, nclasses, cfg)
        return (params, opt, key, samples), mloss

    (params, opt, key, samples), losses = jax.lax.scan(
        epoch, (params, opt, key, samples0), None, length=k)
    return params, opt, key, samples, losses


#: rows a scoring block. A frame scored whole holds every layer's activations
#: for all its rows at once: 12 GB at a million rows under a 2,048-unit layer,
#: which one v5e chip beside the frame and its design does not have (PERF.md
#: section 6, PR 32); a block of 16,384 holds 0.2 GB of them
_SCORE_BLOCK = 16384


def _score_block(X) -> int:
    """The block ``_dl_forward_score`` takes for this design: rows in blocks
    where the design sits on ONE device and is longer than a block, whole (0)
    on a mesh, where each device already holds only its share of the rows and
    a slice of the row axis would gather them."""
    one_device = len(X.sharding.device_set) == 1
    return _SCORE_BLOCK if one_device and X.shape[0] > _SCORE_BLOCK else 0


@partial(jax.jit, static_argnames=("act", "block"))
def _dl_forward_score(params, X, act: str, block: int = 0):
    """The network's outputs for every row of ``X``; ``block`` rows at a time
    where it is given (``_score_block``). The last block starts where a whole
    block still fits, so it scores some rows of the one before it again, to
    the same values."""
    if not block:
        return _forward(params, X, act)
    rows = X.shape[0]

    def body(i, out):
        start = jnp.minimum(i * block, rows - block)
        part = _forward(
            params, jax.lax.dynamic_slice_in_dim(X, start, block, axis=0), act)
        return jax.lax.dynamic_update_slice_in_dim(out, part, start, axis=0)

    out = jnp.zeros((rows, params["b"][-1].shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, -(-rows // block), body, out)


@partial(jax.jit, static_argnames=("act",))
def _dl_reconstruction_mse(params, X, act: str):
    out = _forward(params, X, act)
    return ((out - X) ** 2).mean(axis=1)


# ---------------------------------------------------------------------------
# Model / Builder
# ---------------------------------------------------------------------------

_LOGGED: set[str] = set()


def _log_once(message: str) -> None:
    if message not in _LOGGED:
        _LOGGED.add(message)
        logging.getLogger("h2o3_tpu").info("deeplearning: %s", message)


class DeepLearningModel(Model):
    algo = "deeplearning"

    def _score_raw(self, frame: Frame) -> jax.Array:
        X = self.data_info.expand(frame)
        out = _dl_forward_score(self.output["params"], X, self.output["act"],
                                _score_block(X))
        if self.is_classifier:
            return jax.nn.softmax(out, axis=-1)
        if self.params.get("autoencoder"):
            return out
        return out[:, 0]

    def anomaly(self, frame: Frame) -> Frame:
        """Per-row reconstruction MSE (reference: ``DeepLearningModel
        .scoreAutoEncoder``, anomaly detection use of autoencoders)."""
        if not self.params.get("autoencoder"):
            raise ValueError("anomaly() requires autoencoder=True")
        X = self.data_info.expand(frame)
        mse = _dl_reconstruction_mse(self.output["params"], X, self.output["act"])
        return Frame(["Reconstruction.MSE"],
                     [Vec.from_device(mse, frame.nrows, VecType.NUM)])

    def predict(self, frame: Frame) -> Frame:
        if self.params.get("autoencoder"):
            # reconstruction in the expanded space, named after coefficients
            out = self._score_raw(frame)
            names = [f"reconstr_{n}" for n in self.data_info.coef_names]
            vecs = [Vec.from_device(out[:, i], frame.nrows, VecType.NUM)
                    for i in range(out.shape[1])]
            return Frame(names, vecs)
        return super().predict(frame)


class DeepLearning(ModelBuilder):
    """h2o-py surface: ``H2ODeepLearningEstimator``."""

    algo = "deeplearning"

    @classmethod
    def defaults(cls) -> dict:
        return dict(
            super().defaults(),
            hidden=[200, 200],
            epochs=10.0,
            activation="Rectifier",
            adaptive_rate=True,
            rho=0.99,
            epsilon=1e-8,
            rate=0.005,
            rate_annealing=1e-6,
            rate_decay=1.0,
            momentum_start=0.0,
            momentum_ramp=1e6,
            momentum_stable=0.0,
            nesterov_accelerated_gradient=True,
            input_dropout_ratio=0.0,
            hidden_dropout_ratios=None,     # default 0.5 when *WithDropout
            l1=0.0,
            l2=0.0,
            max_w2=3.4028235e38,
            loss="Automatic",               # CrossEntropy|Quadratic|Absolute|Huber
            huber_alpha=0.9,                # kept for API parity (delta fixed = 1)
            mini_batch_size=32,             # reference default 1 (Hogwild row-at-
                                            # a-time); vectorized minibatch here
            standardize=True,
            use_all_factor_levels=True,
            initial_weight_distribution="UniformAdaptive",
            initial_weight_scale=1.0,
            autoencoder=False,
            score_each_iteration=False,
            # the reference's, with this loop's meaning of each in the
            # module docstring
            train_samples_per_iteration=-2,
            classification_stop=0.0,
            ignore_const_cols=True,
            # elastic local-SGD (docs/RELIABILITY.md "Elastic training"):
            # elastic = number of requested workers (0 = off; clamped to
            # the mesh-slice layout), local_steps = local epochs each
            # worker runs between parameter-averaging rounds (0 coerces
            # to 1 — average every epoch)
            elastic=0,
            local_steps=1,
        )

    unsupervised = False

    def train(self, x=None, y=None, training_frame=None, validation_frame=None,
              weights=None):
        self.unsupervised = bool(self.params.get("autoencoder"))
        return super().train(x=x, y=y, training_frame=training_frame,
                             validation_frame=validation_frame, weights=weights)

    def _init_params(self, key, sizes: list[int], act: str):
        dist = str(self.params["initial_weight_distribution"]).lower()
        scale = float(self.params["initial_weight_scale"])
        Ws, bs = [], []
        n_layers = len(sizes) - 1
        for i in range(n_layers):
            fan_in, fan_out = sizes[i], sizes[i + 1]
            width = fan_out
            if act == "maxout" and i < n_layers - 1:
                width = 2 * fan_out
            key, sub = jax.random.split(key)
            if dist == "uniformadaptive":
                lim = np.sqrt(6.0 / (fan_in + fan_out))
                W = jax.random.uniform(sub, (fan_in, width), jnp.float32, -lim, lim)
            elif dist == "uniform":
                W = jax.random.uniform(sub, (fan_in, width), jnp.float32, -scale, scale)
            else:  # normal
                W = scale * jax.random.normal(sub, (fan_in, width), jnp.float32)
            Ws.append(W)
            bs.append(jnp.zeros(width, jnp.float32))
        return {"W": Ws, "b": bs}

    def supports_auto_recovery(self) -> bool:
        # elastic builds survive faults through MEMBERSHIP (ejection +
        # shard reassignment), not snapshots — advertising auto_recovery
        # there would promise a resume path the round engine doesn't write
        return not int(self.params.get("elastic") or 0)

    def validate_request(self) -> None:
        super().validate_request()
        el = self.params.get("elastic")
        if el is not None and int(el) < 0:
            raise ValueError("elastic must be >= 0 (worker count; 0 = off)")
        ls = self.params.get("local_steps")
        if ls is not None and int(ls) < 0:
            raise ValueError("local_steps must be >= 0")
        if int(self.params["train_samples_per_iteration"]) < -2:
            raise ValueError("train_samples_per_iteration must be -2 (auto), "
                             "-1 (all rows), 0 (one epoch) or a row count")

    def _note_iteration_params(self, classifier: bool) -> None:
        """Say once a process what this loop does with a value it cannot
        honour (module docstring); refuse what the reference refuses."""
        self.validate_request()
        tspi = int(self.params["train_samples_per_iteration"])
        if tspi > 0:
            _log_once(f"train_samples_per_iteration={tspi} sets nothing "
                      "here: every update averages exactly, and the loop "
                      "reports and checkpoints at epoch boundaries")
        stop = float(self.params["classification_stop"])
        if classifier and stop >= 0:
            _log_once(f"classification_stop={stop:g} is not honoured: this "
                      "loop does not score between epochs and trains the "
                      "epochs asked for (-1 is what it does)")

    def _prepare(self, frame: Frame, x, y, weights) -> SimpleNamespace:
        """Everything a build holds before its first update: the design
        matrix and its ``DataInfo``, response and weights, layer sizes,
        initial (or resumed) parameters, zeroed optimiser state, the PRNG key
        the epochs start from and the static hyperparameter tuple ``cfg`` of
        ``_epoch_steps``."""
        p = self.params
        act, act_dropout = _act_kind(p["activation"])
        autoenc = bool(p["autoencoder"])
        di = DataInfo.make(frame, x, standardize=p["standardize"],
                           use_all_factor_levels=p["use_all_factor_levels"],
                           ignore_const_cols=bool(p["ignore_const_cols"]))
        X = di.expand(frame)
        K = X.shape[1]

        if autoenc:
            yy, w = jnp.zeros(X.shape[0], jnp.float32), weights
            nclasses, loss = 0, "quadratic"
            domain = None
        else:
            yvec = frame.vec(y)
            yy, valid = response_as_float(yvec)
            w = weights * valid
            nclasses = yvec.cardinality() if yvec.is_categorical else 0
            domain = yvec.domain if yvec.is_categorical else None
            loss = str(p["loss"]).lower()
            if loss == "automatic":
                loss = "crossentropy" if nclasses else "quadratic"
            if nclasses and loss != "crossentropy":
                raise ValueError("classification requires CrossEntropy loss")
            if not nclasses and loss == "crossentropy":
                raise ValueError("CrossEntropy loss requires a categorical "
                                 "response (reference: DeepLearningParameters "
                                 "validation)")
        yy = jnp.where(w > 0, yy, 0.0)

        hidden = [int(h) for h in p["hidden"]]
        out_dim = K if autoenc else (nclasses if nclasses >= 2 else 1)
        sizes = [K] + hidden + [out_dim]
        seed = int(p.get("seed") or -1)
        key = jax.random.PRNGKey(seed if seed >= 0 else 5318008)
        key, init_key = jax.random.split(key)
        cp = self._resolve_checkpoint()
        done_ep = 0
        samples0 = 0.0
        if cp is not None:
            # resume from the prior model's weights (reference:
            # DeepLearning.java:348 checkpoint path: continue training the
            # same topology on more epochs). An auto-recovery snapshot
            # additionally carries epochs_done, so a crashed build resumes
            # with only the REMAINING epochs instead of the full budget.
            if cp.output["sizes"] != sizes or cp.output["act"] != act:
                raise ValueError("checkpoint topology/activation differs; "
                                 "hidden/activation are immutable across resume")
            params = cp.output["params"]
            key = jax.random.fold_in(key, 1 + int(cp.output["samples_trained"]))
            done_ep = int(cp.output.get("epochs_done") or 0)
            samples0 = float(cp.output.get("samples_trained") or 0.0)
        else:
            params = self._init_params(init_key, sizes, act)

        zeros = jax.tree.map(jnp.zeros_like, params)
        opt = {"Eg": zeros, "Edx": jax.tree.map(jnp.zeros_like, params),
               "v": jax.tree.map(jnp.zeros_like, params)}

        hid_drops = p["hidden_dropout_ratios"]
        if hid_drops is not None and not act_dropout:
            raise ValueError("hidden_dropout_ratios require a *WithDropout "
                             "activation (reference: DeepLearningParameters "
                             "validation)")
        if hid_drops is None:
            hid_drops = [0.5] * len(hidden) if act_dropout else [0.0] * len(hidden)
        if len(hid_drops) != len(hidden):
            raise ValueError("hidden_dropout_ratios must match hidden length")
        cfg = (bool(p["adaptive_rate"]), float(p["rho"]), float(p["epsilon"]),
               float(p["rate"]), float(p["rate_annealing"]), float(p["rate_decay"]),
               float(p["momentum_start"]), float(p["momentum_ramp"]),
               float(p["momentum_stable"]), bool(p["nesterov_accelerated_gradient"]),
               float(p["l1"]), float(p["l2"]), float(p["max_w2"]),
               float(p["input_dropout_ratio"]), tuple(float(d) for d in hid_drops),
               1.0)

        return SimpleNamespace(
            di=di, X=X, yy=yy, w=w, act=act, autoenc=autoenc, loss=loss,
            nclasses=nclasses, domain=domain, sizes=sizes, params=params,
            opt=opt, key=key, cfg=cfg, done_ep=done_ep, samples0=samples0)

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> DeepLearningModel:
        p = self.params
        autoenc = bool(p["autoencoder"])
        self._note_iteration_params(classifier=not autoenc and
                                    frame.vec(y).is_categorical)
        with timed_event("phase", f"{self.algo}:prepare"):
            prep = self._prepare(frame, x, y, weights)
        act, di, X, yy, w = prep.act, prep.di, prep.X, prep.yy, prep.w
        loss, nclasses, domain = prep.loss, prep.nclasses, prep.domain
        sizes, params, opt, key, cfg = (prep.sizes, prep.params, prep.opt,
                                        prep.key, prep.cfg)
        done_ep, samples0 = prep.done_ep, prep.samples0
        _tm.DL_PARAMETERS.set(sum(
            int(a.size) for a in jax.tree.leaves(params)))

        if int(p.get("elastic") or 0):
            # elastic local-SGD: k slice-leased workers train K local
            # epochs per round on their own shard and average parameters
            # at round boundaries, under elastic membership
            # (parallel/elastic.py; docs/RELIABILITY.md)
            return self._fit_elastic(job, frame, y, di, X, yy, w, act,
                                     loss, nclasses, domain, cfg, autoenc,
                                     params, key, sizes, done_ep, samples0)

        plen = X.shape[0]
        B = min(max(int(p["mini_batch_size"]), 1), plen)
        nb, whole, last = _epoch_plan(float(p["epochs"]), plen, B)

        samples = jnp.float32(samples0)
        k_mega = megastep_k()
        epoch_losses = []        # [k] device arrays; fetched once post-loop
        ep = 0
        # remaining after auto-resume; a partial epoch is the last one
        n_epochs = max(whole + bool(last) - done_ep, 0)
        dispatches = 0
        import time as _time
        from h2o3_tpu.models.job import JobCancelled
        from h2o3_tpu.ops.map_reduce import retrying
        from h2o3_tpu.persist.recovery import checkpoint_every
        recovery = getattr(self, "_build_recovery", None)
        ckpt_every = checkpoint_every()
        last_snap = 0

        def _snapshot(epochs_now: int) -> None:
            pm = DeepLearningModel(
                key=f"{self.model_id or self.algo}_autockpt",
                params=ModelParameters(p), data_info=di,
                response_column=None if autoenc else y,
                response_domain=domain,
                output=dict(params=params, act=act, sizes=sizes,
                            score_history=[],
                            samples_trained=float(jax.device_get(samples)),
                            epochs_done=done_ep + epochs_now))
            recovery.snapshot(pm, progress=done_ep + epochs_now,
                              target=done_ep + n_epochs)

        with timed_event("phase", f"{self.algo}:epochs"):
            while ep < n_epochs:
                if job.should_stop:
                    # cooperative deadline/cancel between megasteps: trained
                    # epochs are kept (partial model, job CANCELLED)
                    job.keep_partial()
                    break
                # K epochs per compiled dispatch (trailing chunk compiles its own
                # smaller K once); shuffle + minibatching run inside the program,
                # so the host dispatches WORK, not steps
                if last and ep == n_epochs - 1:
                    kk, steps = 1, last
                else:
                    kk, steps = min(k_mega, n_epochs - bool(last) - ep), nb
                t0 = _time.time_ns()
                _in = (params, opt, key, samples)
                with timed_event("iteration", "dl_epoch"):
                    # retried on transient dispatch failure: the megastep is
                    # functional over its inputs, so a re-run is exact
                    params, opt, key, samples, losses_k = retrying(
                        "dl_epochs", lambda: _train_epochs(
                            *_in[:2], X, yy, w, *_in[2:],
                            act, loss, nclasses, cfg, kk, steps, B, autoenc))
                # NO per-epoch fetch: the loss series stays on device and is
                # fetched in one batched transfer below, so megasteps pipeline
                epoch_losses.append(losses_k)
                dispatches += 1
                ep += kk
                _tm.DL_UPDATES.inc(kk * steps)
                _tm.DL_SAMPLES.inc(kk * steps * B)
                # per-EPOCH latency: megastep wall amortized over its epochs, so
                # the histogram count keeps matching epochs (same contract as
                # the GLM loops; like the old per-epoch path this is dispatch
                # enqueue time — the loss fetch below pays the real wait)
                dt = (_time.time_ns() - t0) / 1e9
                for _ in range(kk):
                    _tm.ITER_SECONDS.labels(loop="dl_epoch").observe(dt / kk)
                if recovery is not None and ep - last_snap >= ckpt_every:
                    _snapshot(ep)
                    last_snap = ep
                try:
                    job.update(ep / max(n_epochs, 1), f"epoch {ep}/{n_epochs}")
                except JobCancelled:
                    job.keep_partial()
                    break           # partial-result algorithm: keep the epochs
                if job.cancelled:
                    break
            if recovery is not None and job.should_stop and ep > last_snap:
                _snapshot(ep)       # CANCELLED builds stay resumable
            publish_dispatch_audit(self, "dl_epoch", iterations=max(ep, 1),
                                   host_syncs=1, device_dispatches=dispatches)
            # the build's one fetch: it waits for every dispatch above, so the
            # span that ends here is a synced time
            score_history = [
                {"epoch": i + 1, "train_loss": float(v)}
                for i, v in enumerate(np.concatenate(
                    [np.atleast_1d(np.asarray(a))
                     for a in jax.device_get(epoch_losses)])
                    if epoch_losses else [])]

        model = DeepLearningModel(
            key=make_model_key(self.algo, self.model_id),
            params=ModelParameters(p),
            data_info=di,
            response_column=None if autoenc else y,
            response_domain=domain,
            output=dict(params=params, act=act, sizes=sizes,
                        score_history=score_history,
                        samples_trained=float(jax.device_get(samples))),
        )
        return model

    # -- elastic local-SGD (docs/RELIABILITY.md "Elastic training") ----------

    def _fit_elastic(self, job: Job, frame: Frame, y, di, X, yy, w,
                     act: str, loss: str, nclasses: int, domain, cfg: tuple,
                     autoenc: bool, params0, key, sizes, done_ep: int,
                     samples0: float) -> DeepLearningModel:
        """Local-SGD rounds over an elastic worker group.

        Workers are mesh slices leased for the group's lifetime; each runs
        ``local_steps`` whole epochs (the ``_train_epochs`` megastep) on its
        own contiguous data shard per round, then live workers' parameters
        are weighted-averaged (weights = shard weight-sums, renormalized
        over whoever reported) and re-broadcast. A worker that faults,
        exhausts its dispatch-retry budget, blows the round deadline, or
        stops heartbeating is EJECTED: its shards are reassigned to
        survivors at the next boundary; a (re)joining worker catches up by
        cloning the latest average (every round thunk starts from the
        broadcast). Below the quorum the build cancels with partial results
        (``Job.keep_partial``). Fixed membership + fixed seeds is
        reproducibility-identical across reruns: shard assignment, worker
        PRNG streams (``fold_in(key, wid)``), and the wid-ordered host-side
        float64 average are all deterministic — ejection changes the
        averaging sequence, so parity holds only at fixed membership."""
        from h2o3_tpu.models.job import JobCancelled
        from h2o3_tpu.ops.map_reduce import retrying
        from h2o3_tpu.orchestration.scheduler import MeshScheduler
        from h2o3_tpu.parallel.elastic import (ElasticGroup,
                                               min_workers_from_env)
        from h2o3_tpu.parallel.mesh import (ROWS, num_devices,
                                            replicated_sharding,
                                            row_sharding)

        p = self.params
        k_req = max(int(p["elastic"]), 1)
        local_k = max(int(p.get("local_steps") or 0), 1)
        scheduler = MeshScheduler(slices=k_req)
        if scheduler.n > 1:
            k = scheduler.n
        else:
            # degenerate layout: on a single-device mesh threads overlap
            # safely (no collectives to rendezvous); on a multi-device mesh
            # one slice means ONE worker — overlapped same-mesh collectives
            # are the documented XLA wedge the slice layout exists to avoid
            k = k_req if num_devices() <= 1 else 1
        slice_ndev = scheduler.meshes[0].shape[ROWS]

        # host-side data: shard the REAL rows contiguously into k*spw equal
        # SUB-shards (several per worker), each padded (zero-weight rows)
        # to a multiple of the slice device count. Identical shapes mean a
        # reassigned shard reuses the survivor's compiled program, and
        # finer granularity means an ejected worker's load spreads ~evenly
        # over the k-1 survivors (one whole-worker shard handed to one
        # survivor would DOUBLE its round wall — the post-ejection
        # throughput floor is k/(k-1), reachable only with sub-shards);
        # workers also heartbeat between sub-shards, so slow and dead
        # separate faster. spw shrinks until each sub-shard still holds a
        # full minibatch.
        n = frame.nrows
        Xh = np.asarray(jax.device_get(X))[:n]
        yh = np.asarray(jax.device_get(yy))[:n]
        wh = np.asarray(jax.device_get(w))[:n]
        B_req = max(int(p["mini_batch_size"]), 1)
        spw = 6 if k > 1 else 1
        while spw > 1 and n // (k * spw) < B_req:
            spw -= 1
        n_shards = k * spw
        base = -(-n // n_shards)                # ceil
        shard_n = -(-base // slice_ndev) * slice_ndev
        host_shards = []
        for i in range(n_shards):
            lo, hi = i * base, min(n, (i + 1) * base)
            m = max(hi - lo, 0)
            Xs = np.zeros((shard_n, Xh.shape[1]), np.float32)
            ys = np.zeros(shard_n, np.float32)
            ws = np.zeros(shard_n, np.float32)
            if m:
                Xs[:m], ys[:m], ws[:m] = Xh[lo:hi], yh[lo:hi], wh[lo:hi]
            host_shards.append({"X": Xs, "y": ys, "w": ws,
                                "wsum": float(ws.sum()), "rows": m})
        B = min(B_req, shard_n)
        nb = shard_n // B
        n_epochs = max(max(int(np.ceil(float(p["epochs"]))), 1) - done_ep, 0)

        key_h = np.asarray(jax.device_get(key))
        avg_h = jax.device_get(params0)         # host pytree of np arrays
        wstate = {wid: {"opt": None, "key": None, "data": {},
                        "samples": float(samples0)}
                  for wid in range(k)}

        group = ElasticGroup(k, scheduler=scheduler, job=job,
                             group_id=job.key,
                             shards={wid: list(range(wid * spw,
                                                     (wid + 1) * spw))
                                     for wid in range(k)})
        group.start()

        def make_step(wid: int, owned: list, kk: int, avg):
            def step():
                st = wstate[wid]
                for sid in [s for s in st["data"] if s not in owned]:
                    st["data"].pop(sid)
                for sid in owned:
                    if sid not in st["data"]:
                        hs = host_shards[sid]
                        st["data"][sid] = (
                            jax.device_put(hs["X"], row_sharding(2)),
                            jax.device_put(hs["y"], row_sharding(1)),
                            jax.device_put(hs["w"], row_sharding(1)))
                rs = replicated_sharding()
                pd = jax.device_put(avg, rs)
                if st["opt"] is None:
                    zeros = jax.tree.map(jnp.zeros_like, pd)
                    st["opt"] = {
                        "Eg": zeros,
                        "Edx": jax.tree.map(jnp.zeros_like, pd),
                        "v": jax.tree.map(jnp.zeros_like, pd)}
                    st["key"] = jax.device_put(
                        jax.random.fold_in(jnp.asarray(key_h), wid), rs)
                opt, kw = st["opt"], st["key"]
                samples_d = jnp.float32(st["samples"])
                shard_losses = []
                for sid in owned:
                    Xd, yd, wd = st["data"][sid]
                    _in = (pd, opt, kw, samples_d)
                    with timed_event("iteration", "dl_epoch"):
                        pd, opt, kw, samples_d, losses_k = retrying(
                            "dl_epochs", lambda: _train_epochs(
                                _in[0], _in[1], Xd, yd, wd, _in[2], _in[3],
                                act, loss, nclasses, cfg, kk, nb, B,
                                autoenc))
                    group.heartbeat(wid)
                    shard_losses.append(losses_k)
                # ONE batched fetch per worker-round (the megastep fetch
                # contract): params + the loss series + the sample counter
                ph, lh, sh = jax.device_get((pd, shard_losses, samples_d))
                st["opt"], st["key"] = opt, kw
                st["samples"] = float(sh)
                wsum = sum(host_shards[sid]["wsum"] for sid in owned)
                la = np.zeros(kk)
                for sid, lk in zip(owned, lh):
                    la += (np.atleast_1d(np.asarray(lk))
                           * (host_shards[sid]["wsum"] / max(wsum, 1e-8)))
                return {"params": ph, "losses": la, "wsum": wsum}
            return step

        quorum = min_workers_from_env()
        epoch_losses: list[float] = []
        ep_done = 0
        rnd = 0
        try:
            while ep_done < n_epochs:
                if job.should_stop:
                    job.keep_partial()
                    break
                live = group.live_workers()
                if len(live) < quorum:
                    # quorum lost: cancel with partial results — the last
                    # average IS the partial model (PR 8 contract)
                    job.cancel()
                    job.keep_partial()
                    break
                kk = min(local_k, n_epochs - ep_done)
                rnd += 1
                thunks = {wid: make_step(wid, owned, kk, avg_h)
                          for wid in live
                          if (owned := group.owned_shards(wid))}
                if not thunks:
                    break
                reports = group.run_round(rnd, thunks)
                if not reports:
                    # everyone missed the boundary — membership was swept;
                    # the quorum check above decides whether to go on
                    continue
                # every host-side reduction iterates wid-SORTED reports:
                # dict order is thread-arrival order, and float sums in
                # arrival order would break rerun bit-reproducibility
                ordered = [reports[w] for w in sorted(reports)]
                tot = sum(r["wsum"] for r in ordered)
                if tot > 0:
                    # wid-ordered float64 weighted average — deterministic,
                    # and renormalized over exactly the reporting workers
                    avg_h = jax.tree.map(
                        lambda *leaves: sum(
                            (r["wsum"] / tot) * np.asarray(lv, np.float64)
                            for r, lv in zip(ordered, leaves))
                        .astype(np.float32),
                        *[r["params"] for r in ordered])
                    for e in range(kk):
                        epoch_losses.append(float(sum(
                            (r["wsum"] / tot) * r["losses"][e]
                            for r in ordered)))
                ep_done += kk
                try:
                    job.update(ep_done / max(n_epochs, 1),
                               f"round {rnd}: epoch {ep_done}/{n_epochs} "
                               f"({len(reports)}/{k} workers)")
                except JobCancelled:
                    job.keep_partial()
                    break
                if job.cancelled:
                    break
        finally:
            group.shutdown()

        publish_dispatch_audit(self, "dl_elastic",
                               iterations=max(ep_done, 1),
                               host_syncs=max(rnd, 1),
                               device_dispatches=max(rnd, 1))
        score_history = [{"epoch": i + 1, "train_loss": v}
                         for i, v in enumerate(epoch_losses)]
        params_final = jax.device_put(avg_h, replicated_sharding())
        # every worker starts its schedule counter at samples0 (checkpoint
        # resume position); the TRAINED total is the sum of deltas
        samples_trained = float(samples0 + sum(
            st["samples"] - samples0 for st in wstate.values()))
        model = DeepLearningModel(
            key=make_model_key(self.algo, self.model_id),
            params=ModelParameters(p),
            data_info=di,
            response_column=None if autoenc else y,
            response_domain=domain,
            output=dict(params=params_final, act=act, sizes=sizes,
                        score_history=score_history,
                        samples_trained=samples_trained,
                        elastic={**group.summary(),
                                 "shards_per_worker": spw}),
        )
        return model

    def _validate(self, frame, x, y):
        if not self.params.get("autoencoder"):
            super()._validate(frame, x, y)

    def _scoring_history(self, model):
        """Per-epoch rows (reference: ``DeepLearningScoringInfo`` →
        ``createScoringHistoryTable``)."""
        hist = model.output.get("score_history") or []
        if not hist:
            return None
        return self._history_table(
            model,
            [("epochs", "double", "%.1f"),
             ("training_loss", "double", "%.5f")],
            [[float(h["epoch"]), float(h["train_loss"])] for h in hist])


class AutoEncoder(DeepLearning):
    """Convenience alias (h2o-py: H2OAutoEncoderEstimator)."""

    @classmethod
    def defaults(cls) -> dict:
        d = super().defaults()
        d["autoencoder"] = True
        d["hidden"] = [20]
        return d
