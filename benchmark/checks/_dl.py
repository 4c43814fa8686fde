"""What the four ``dl_*`` checks share (and ``tests/test_dl_reference.py``,
which holds the program to the same reference at a toy size on the CPU).

- the training rows made again from the seed as ONE uint8 matrix
  (``generators/mnist_like.pixels``: the generator is deterministic, so these
  are the timed builds' own rows; ``build_loop`` does not hand its frame on,
  and a second frame would be 3 GB more on the device), with the reference's
  own column list, means and deviations;
- ``prepared``: what a build holds before its first update
  (``DeepLearning._prepare``: design matrix, initial parameters, key, ``cfg``),
  on the resident training frame (the generator hands the same Frame out
  again); ``start_of`` keeps its initial parameters and key for the checks
  that come later, without the design matrix;
- ``heldout``: fold 2 as a Frame (constant columns in, response domain
  REVERSED) scored by ``model.predict`` once a run, and the same rows as the
  reference reads them;
- ``program_updates``: the timed program itself, ``_train_epochs``, run for
  ``n`` updates of one epoch;
- ``reference_updates``: the plain reference's ``update`` looped over the
  same minibatches under the same masks. THE RANDOM STREAM IS NOT THE
  MATHEMATICS UNDER TEST: the permutation and the dropout masks are drawn
  here from the keys the program uses, through the program's own
  ``_epoch_keys`` and ``_dropout_masks``, and handed to the reference as
  arrays. The loop is a ``lax.fori_loop`` inside ``jax.jit`` over blocks of
  updates (31,250 updates dispatched operation by operation would take
  minutes); the mathematics stays the reference's, float32-highest.
"""

from __future__ import annotations

import types

#: updates a compiled block of the reference's loop
BLOCK = 1250


def generator(ctx):
    from benchmark import plugins
    return plugins.load("generators", ctx.data["generator"])


def reference_module(ctx):
    from benchmark import plugins
    return plugins.load("reference", ctx.config["reference"])


def hyper(params: dict) -> types.SimpleNamespace:
    """The configuration's hyperparameters as the reference takes them, read
    off the builder's parameters by NAME (not off the program's ``cfg``)."""
    hidden = [int(h) for h in params["hidden"]]
    ratios = params.get("hidden_dropout_ratios")
    if ratios is None:
        ratios = [0.5 if str(params["activation"]).endswith("WithDropout")
                  else 0.0] * len(hidden)
    if str(params["activation"]).lower().replace("withdropout", "") != "rectifier":
        raise RuntimeError("dl_mlp_jnp is a rectifier reference")
    if not params["adaptive_rate"] or float(params["l2"]) != 0.0:
        raise RuntimeError("dl_mlp_jnp is an ADADELTA reference with l1 only")
    in_drop = float(params["input_dropout_ratio"])
    return types.SimpleNamespace(
        hidden=hidden, B=int(params["mini_batch_size"]), in_drop=in_drop,
        hid_drops=tuple(float(r) for r in ratios),
        keep=tuple(1.0 - float(r) for r in [in_drop, *ratios]),
        l1=float(params["l1"]), rho=float(params["rho"]),
        eps=float(params["epsilon"]))


def training_rows(ctx) -> types.SimpleNamespace:
    """The training rows as the reference reads them: ``pixels`` uint8
    [rows, 784] and ``labels`` int32 on the device, the non-constant columns
    ``kept`` and their ``mean`` and ``sd`` worked in float64 on the host."""
    if getattr(ctx, "dl_rows", None) is None:
        ref = reference_module(ctx)
        px, labels, _true = generator(ctx).pixels(
            ctx.cell.seed, 0, int(ctx.data["rows"]))
        kept, mean, sd = ref.standardize(px)
        ctx.dl_rows = types.SimpleNamespace(
            pixels=px, labels=labels, kept=kept, mean=mean, sd=sd)
    return ctx.dl_rows


def training_frame(ctx):
    """The frame the window's builds trained on: ``mnist_like.make`` keeps
    its newest training frame and hands it out again for the same arguments
    (a copy would be 3 GB more on the device than any build holds)."""
    return generator(ctx).make(ctx.cell.seed, 0, ctx.data)


def prepared(ctx) -> types.SimpleNamespace:
    """``DeepLearning._prepare`` on the training frame: the design matrix
    the timed builds trained on, their initial parameters, key and ``cfg``.
    Dropped by the caller when done (it holds a design matrix: 3 GB at the
    cell's size)."""
    import jax.numpy as jnp
    frame = training_frame(ctx)
    response = ctx.data["response"]
    builder = ctx.builder(**ctx.config["params"])
    x = [n for n in frame.names if n != response]
    return builder._prepare(frame, x, response,
                            frame.row_mask().astype(jnp.float32))


def start_of(ctx, prep=None):
    """(a build's initial parameters, the key its epochs start from, the
    padded row count its permutations are over), kept on the context."""
    if getattr(ctx, "dl_start", None) is None:
        prep = prepared(ctx) if prep is None else prep
        ctx.dl_start = (prep.params, prep.key, int(prep.X.shape[0]))
    return ctx.dl_start


def heldout(ctx) -> types.SimpleNamespace:
    """Fold 2, scored once: ``proba`` [rows, classes] float64 and
    ``predicted`` class numbers from ``model.predict`` on the Frame (its
    constant columns in, its response domain reversed); ``pixels`` and
    ``labels`` as the reference reads the same rows; ``ceiling`` (error,
    log-loss) of the model that knows their true classes."""
    if getattr(ctx, "dl_heldout", None) is None:
        import numpy as np
        gen = generator(ctx)
        rows = ctx.cell.size(ctx.traffic, "heldout_rows")
        frame = gen.make(ctx.cell.seed, 2, dict(ctx.data, rows=rows,
                                                domain_order="reversed"))
        pred = ctx.model.predict(frame)
        domain = ctx.model.response_domain
        proba = np.stack([np.asarray(pred.vec(f"p{lvl}").to_numpy()[:rows],
                                     np.float64) for lvl in domain], axis=1)
        # a label is a NAME of the training domain, whatever the frame's codes
        named = np.asarray(pred.vec("predict").labels()[:rows]).astype(np.int64)
        proba = proba[:, np.argsort([int(lvl) for lvl in domain])]
        px, labels, true = gen.pixels(ctx.cell.seed, 2, rows)
        ctx.dl_heldout = types.SimpleNamespace(
            proba=proba, predicted=named, pixels=px,
            labels=np.asarray(labels), ceiling=gen.ceiling(labels, true))
    return ctx.dl_heldout


def reference_proba(ref, theta, pixels, rows, block: int = 16384):
    """The reference's class probabilities of raw ``pixels``, a block of
    rows at a time (its forward pass whole would hold every activation of
    200,000 rows), under ``rows``' columns, means and deviations."""
    import jax
    import numpy as np

    import jax.numpy as jnp
    moments = (jnp.asarray(rows.kept), jnp.asarray(rows.mean, jnp.float32),
               jnp.asarray(rows.sd, jnp.float32))

    @jax.jit
    def proba(theta, moments, px):
        return ref.predict_proba(theta, ref.design(px, *moments))

    return np.concatenate([np.asarray(proba(theta, moments, pixels[a:a + block]))
                           for a in range(0, pixels.shape[0], block)])


def program_updates(prep, n: int, B: int):
    """(parameters, optimiser state) after the first ``n`` updates of an
    epoch of the timed program, from ``prep``'s initial state."""
    import jax.numpy as jnp

    from h2o3_tpu.models import deeplearning as dl
    params, opt, _key, _samples, _losses = dl._train_epochs(
        prep.params, prep.opt, prep.X, prep.yy, prep.w, prep.key,
        jnp.float32(prep.samples0), prep.act, prep.loss, prep.nclasses,
        prep.cfg, 1, int(n), int(B), prep.autoenc)
    return params, opt


def reference_updates(ref, theta, rows, key, plen: int, n: int, hp,
                      state=None, block: int = BLOCK):
    """(theta, state, the next epoch's key) after the first ``n`` updates of
    the reference from ``theta`` on the minibatches the program's epoch that
    starts at ``key`` takes out of ``plen`` rows (``rows.pixels`` padded with
    zero-weight rows up to it)."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models import deeplearning as dl

    real = rows.pixels.shape[0]
    next_key, pk, ek = dl._epoch_keys(key)
    perm = jax.random.permutation(pk, plen)
    widths = [len(rows.kept)] + list(hp.hidden)
    moments = (jnp.asarray(rows.kept), jnp.asarray(rows.mean, jnp.float32),
               jnp.asarray(rows.sd, jnp.float32))
    state = ref.zeros_like(theta) if state is None else state

    # one executable whatever the count and the seed (a traced trip count; the
    # permutation and the moments are arguments, not constants): 8 updates, 64
    # and a whole epoch's blocks compile once and load from the cache after
    @jax.jit
    def run(theta, state, k, perm, moments, pixels, labels, first, count):
        kept, mean, sd = moments

        def step(i, carry):
            theta, state, k = carry
            k, sub = jax.random.split(k)
            masks = [m.astype(jnp.float32) for m in dl._dropout_masks(
                sub, hp.B, widths, hp.in_drop, hp.hid_drops)]
            batch = jax.lax.dynamic_slice_in_dim(perm, (first + i) * hp.B, hp.B)
            live = batch < real
            at = jnp.minimum(batch, real - 1)
            x = ref.design(pixels[at], kept, mean, sd)
            theta, state, _loss = ref.update(
                theta, state, x, labels[at], live.astype(jnp.float32), masks,
                keep=hp.keep, l1=hp.l1, rho=hp.rho, eps=hp.eps)
            return theta, state, k
        return jax.lax.fori_loop(0, count, step, (theta, state, k))

    k = ek
    for start in range(0, n, block):
        theta, state, k = run(theta, state, k, perm, moments, rows.pixels,
                              rows.labels, jnp.int32(start),
                              jnp.int32(min(block, n - start)))
    return theta, state, next_key


def reference_at(ctx, n: int):
    """(theta, state) of the reference after the first ``n`` updates of a
    build's first epoch, from the build's own initial parameters; kept."""
    kept = ctx.__dict__.setdefault("dl_reference_at", {})
    if n not in kept:
        start, key, plen = start_of(ctx)
        kept[n] = reference_updates(
            reference_module(ctx), start, training_rows(ctx), key, plen, n,
            hyper(ctx.params), block=min(n, BLOCK))[:2]
    return kept[n]


def reference_trained(ctx):
    """(theta, updates) of the reference trained as a build trains: every
    epoch of the configuration's ``epochs`` on the whole training frame, from
    the build's own initial parameters; kept (about as long as a build)."""
    if getattr(ctx, "dl_reference_trained", None) is None:
        from h2o3_tpu.models import deeplearning as dl
        ref, hp = reference_module(ctx), hyper(ctx.params)
        theta, key, plen = start_of(ctx)
        rows = training_rows(ctx)
        nb, whole, last = dl._epoch_plan(float(ctx.params["epochs"]), plen, hp.B)
        state, updates = None, 0
        for n in [nb] * whole + [last] * bool(last):
            theta, state, key = reference_updates(ref, theta, rows, key, plen,
                                                  n, hp, state=state)
            updates += n
        ctx.dl_reference_trained = (theta, updates)
    return ctx.dl_reference_trained


def scaled_errors(got, want, start=None, norm: str = "max") -> list[float]:
    """Per array: the difference over the CHANGE the reference made to it
    (from ``start``; from zero without one), both as the largest absolute
    element (``"max"``) or as root sums of squares (``"l2"``). A parameter
    moves by 1e-2 of itself in 64 updates, so an error measured against the
    parameter's own size would hide everything."""
    import jax
    import numpy as np
    g = [np.asarray(a, np.float64) for a in jax.tree.leaves(got)]
    w = [np.asarray(a, np.float64) for a in jax.tree.leaves(want)]
    s = ([np.asarray(a, np.float64) for a in jax.tree.leaves(start)]
         if start is not None else [0.0] * len(w))
    size = ((lambda a: float(np.max(np.abs(a)))) if norm == "max"
            else (lambda a: float(np.sqrt(np.sum(a * a)))))
    return [size(a - b) / max(size(b - c), 1e-30) for a, b, c in zip(g, w, s)]
