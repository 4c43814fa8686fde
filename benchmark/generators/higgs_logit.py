"""Generator ``higgs_logit``: HIGGS-shaped data, made ON the device.

One jitted call draws every column under the frame's own row sharding at
``padded_len(rows)``; the columns are wrapped with the program's public
constructors (``Vec.from_device`` / ``Frame``). No host copy of a training
frame ever exists. 28 (or ``data['features']``) float32 standard normals
``x0..`` and a binary response drawn from the logit of bench.py's
``_higgs_frame`` (copied; the original is listed for deletion in PERF.md
section 7).

A generator is a file here with ``make(seed, fold, data) -> Frame`` and,
where the response is drawn from a known score, ``ideal_score(columns)``:
the ceiling no model beats. ``fold`` separates the draws a cell needs from
one ``--seed``: 0 the training frame, 1 the sample the plain reference
trains on, 2 the held-out rows nothing trains on.
"""

from __future__ import annotations


def ideal_score(cols):
    """The generating logit from a sequence of at least six columns — numpy
    or jax."""
    return (1.2 * cols[0] - 0.8 * cols[1] + 0.5 * cols[2] + 0.3 * cols[3]
            + 0.2 * cols[4] * cols[5])


def _columns(seed: int, fold: int, rows: int, features: int):
    """(feature columns [plen] float32 x ``features``, response codes [plen]
    int32) on the device, row-sharded; padding rows are NaN / CAT_NA."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.frame.types import CAT_NA
    from h2o3_tpu.frame.vec import padded_len
    from h2o3_tpu.parallel.mesh import row_sharding

    plen = padded_len(rows)
    sharding = row_sharding(1)

    def draw(key):
        live = jnp.arange(plen) < rows
        cols = [jax.random.normal(jax.random.fold_in(key, j), (plen,),
                                  jnp.float32) for j in range(features)]
        u = jax.random.uniform(jax.random.fold_in(key, features), (plen,))
        y = (u < jax.nn.sigmoid(ideal_score(cols))).astype(jnp.int32)
        return (tuple(jnp.where(live, c, jnp.nan) for c in cols),
                jnp.where(live, y, CAT_NA))

    key = jax.random.fold_in(jax.random.PRNGKey(seed), fold)
    fn = jax.jit(draw, out_shardings=((sharding,) * features, sharding))
    return fn(key)


def make(seed: int, fold: int, data: dict):
    """A Frame of ``data['rows']`` x ``data['features']`` float columns
    ``x0..`` plus the categorical response (domain ``b``/``s``)."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.types import VecType
    from h2o3_tpu.frame.vec import Vec

    rows, features = int(data["rows"]), int(data["features"])
    cols, y = _columns(seed, fold, rows, features)
    names = [f"x{j}" for j in range(features)] + [data["response"]]
    vecs = [Vec.from_device(c, rows) for c in cols]
    vecs.append(Vec.from_device(y, rows, VecType.CAT, domain=("b", "s")))
    return Frame(names, vecs)
