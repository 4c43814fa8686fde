"""Generator ``airline_delay``: the schema of szilard/benchm-ml's airline
on-time data (``1-linear``), made ON the device.

Six categorical columns and two numeric ones, in the source's order, and the
binary response ``dep_delayed_15min``:

    Month 12 levels, DayofMonth 31, DayOfWeek 7   near uniform
    UniqueCarrier 22, Origin 300, Dest 300        Zipf, exponent 1
    DepTime   hhmm, 0500-2359                      float32
    Distance  miles, log-normal, 30-4960           float32

One-hot expanded without the first level of each column that is 11 + 30 + 6
+ 21 + 299 + 299 + 2 = 668 columns. Which level of a Zipf column is how
frequent is a fixed shuffle (a constant of the generator): level 0, the one
the expansion drops, is of middling rank, as the alphabetically first airport
of the real data is. The response is drawn from a logit that is linear in the
expanded design, so a logistic GLM is the right model and ``ideal_score`` is
the ceiling. Its per-level effects are CONSTANTS of the generator (drawn once
from a fixed numpy stream): the seed draws rows, not effects, so every seed
converges in the same number of IRLS iterations. Positive rate near 20%.

Everything above is written from memory of the data set (no network): the
configuration lists it under ``assumed``.

``make(seed, fold, data)`` as in ``higgs_logit``: fold 0 the training frame,
1 a reference's sample, 2 held-out rows. Two optional keys of ``data``:

- ``levels_for_rows``: the row count the cardinalities are capped for
  (default ``rows``). A column never has more levels than that count // 200,
  so that in the CPU rehearsal (8,192 rows: 40 airports) no level is empty
  or separable at ``lambda`` 0. At a million rows the cap is 5,000 and does
  not bind. A held-out frame passes the TRAINING frame's row count, so both
  have the same levels.
- ``domain_order``: ``"reversed"`` writes every categorical column with its
  domain, and so its codes, in reverse order: the same rows, for a scoring
  frame that has to be adapted to the training layout by level name.
"""

from __future__ import annotations

import numpy as np

RESPONSE_DOMAIN = ("N", "Y")
#: column, levels at the source, frequency law, sd of the per-level effects
CATEGORICALS = (
    ("Month", 12, "uniform", 0.15),
    ("DayofMonth", 31, "uniform", 0.04),
    ("DayOfWeek", 7, "uniform", 0.10),
    ("UniqueCarrier", 22, "zipf", 0.25),
    ("Origin", 300, "zipf", 0.30),
    ("Dest", 300, "zipf", 0.20),
)
NUMERICS = ("DepTime", "Distance")
NAMES = tuple(c[0] for c in CATEGORICALS) + NUMERICS
CARRIERS = ("AA", "AQ", "AS", "B6", "CO", "DH", "DL", "EV", "F9", "FL", "HA",
            "HP", "MQ", "NW", "OH", "OO", "TZ", "UA", "US", "WN", "XE", "YV")
#: the generating logit's constants
INTERCEPT = -1.36
DEPTIME_SLOPE = 0.7 / 600.0       # 0.7 of a logit per 600 hhmm units, from 13:00
DEPTIME_CENTRE = 1300.0
DISTANCE_SLOPE = 0.05 / 600.0     # a twentieth of a logit per 600 miles
DISTANCE_CENTRE = 700.0
#: rows a level needs at least, as a share of the frame (the cap above)
ROWS_PER_LEVEL = 200
_STREAM = 20050101                # the fixed numpy stream of the constants


def cardinalities(levels_for_rows: int) -> tuple[int, ...]:
    cap = max(int(levels_for_rows) // ROWS_PER_LEVEL, 2)
    return tuple(min(card, cap) for _, card, _, _ in CATEGORICALS)


def domain(column: str, card: int) -> tuple[str, ...]:
    if column == "UniqueCarrier":
        return CARRIERS[:card]
    if column in ("Origin", "Dest"):
        return tuple(f"A{j:03d}" for j in range(card))
    return tuple(f"c-{j + 1}" for j in range(card))


def constants(cards: tuple[int, ...]):
    """(probabilities by level, effects by level) of each categorical
    column, float64. Level 0's effect is 0: it is the level the expansion
    drops, so the generating coefficients are the effects themselves."""
    rng = np.random.RandomState(_STREAM)
    probs, effects = [], []
    for (_name, full, law, sd), card in zip(CATEGORICALS, cards):
        # drawn at the source's cardinality and cut, so that a capped column
        # keeps the first levels' constants
        rank = rng.permutation(full)
        eff = rng.normal(0.0, sd, full)
        weight = 1.0 / (1.0 + rank) if law == "zipf" else np.ones(full)
        weight, eff = weight[:card], eff[:card] - eff[0]
        probs.append(weight / weight.sum())
        effects.append(eff)
    return probs, effects


def ideal_score(cols, cards: tuple[int, ...] | None = None):
    """The generating logit from the eight predictor columns in the frame's
    order (codes in the generator's own domain order) — numpy or jax.
    ``cards``: the columns' cardinalities where the rehearsal's cap cut
    them; the source's by default."""
    cats, (deptime, distance) = cols[:len(CATEGORICALS)], cols[-2:]
    _probs, effects = constants(cards or cardinalities(1 << 30))
    if isinstance(deptime, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp
    score = (INTERCEPT + DEPTIME_SLOPE * (deptime - DEPTIME_CENTRE)
             + DISTANCE_SLOPE * (distance - DISTANCE_CENTRE))
    for codes, eff in zip(cats, effects):
        score = score + xp.asarray(eff, xp.float32)[codes.astype(xp.int32)]
    return score


def _columns(seed: int, fold: int, rows: int, cards: tuple[int, ...]):
    """(six code columns int32, two float32 columns, response codes int32),
    each [plen] on the device, row-sharded; padding rows are CAT_NA / NaN."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.frame.types import CAT_NA
    from h2o3_tpu.frame.vec import padded_len
    from h2o3_tpu.parallel.mesh import row_sharding

    plen = padded_len(rows)
    sharding = row_sharding(1)
    probs, _effects = constants(cards)
    # a level's code is the count of the cumulative probabilities at or
    # under the row's uniform draw: compares fused over the rows, no
    # [rows, levels] buffer and no gather
    cdfs = [jnp.asarray(np.cumsum(p)[:-1], jnp.float32) for p in probs]

    def draw(key):
        live = jnp.arange(plen) < rows
        cats = []
        for j, cdf in enumerate(cdfs):
            u = jax.random.uniform(jax.random.fold_in(key, j), (plen,))
            cats.append((u[None, :] >= cdf[:, None]).sum(0, dtype=jnp.int32))
        k = len(cdfs)
        minute = jnp.floor(300.0 + 1140.0 * jax.random.uniform(
            jax.random.fold_in(key, k), (plen,)))
        deptime = jnp.floor(minute / 60.0) * 100.0 + jnp.mod(minute, 60.0)
        distance = jnp.clip(jnp.round(jnp.exp(6.4 + 0.7 * jax.random.normal(
            jax.random.fold_in(key, k + 1), (plen,)))), 30.0, 4960.0)
        score = ideal_score(cats + [deptime, distance], cards)
        u = jax.random.uniform(jax.random.fold_in(key, k + 2), (plen,))
        y = (u < jax.nn.sigmoid(score)).astype(jnp.int32)
        return (tuple(jnp.where(live, c, CAT_NA) for c in cats),
                tuple(jnp.where(live, c, jnp.nan).astype(jnp.float32)
                      for c in (deptime, distance)),
                jnp.where(live, y, CAT_NA))

    key = jax.random.fold_in(jax.random.PRNGKey(seed), fold)
    fn = jax.jit(draw, out_shardings=((sharding,) * len(cdfs),
                                      (sharding,) * 2, sharding))
    return fn(key)


def make(seed: int, fold: int, data: dict):
    """A Frame of ``data['rows']`` rows: the eight predictors and the
    categorical response ``data['response']`` (domain ``N``/``Y``)."""
    import jax.numpy as jnp

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.types import CAT_NA, VecType
    from h2o3_tpu.frame.vec import Vec

    rows = int(data["rows"])
    cards = cardinalities(data.get("levels_for_rows", rows))
    reverse = data.get("domain_order") == "reversed"
    cats, nums, y = _columns(seed, fold, rows, cards)
    vecs = []
    for (name, *_), card, codes in zip(CATEGORICALS, cards, cats):
        dom = domain(name, card)
        if reverse:
            dom = dom[::-1]
            codes = jnp.where(codes == CAT_NA, CAT_NA, card - 1 - codes)
        vecs.append(Vec.from_device(codes, rows, VecType.CAT, domain=dom))
    vecs += [Vec.from_device(c, rows) for c in nums]
    vecs.append(Vec.from_device(y, rows, VecType.CAT, domain=RESPONSE_DOMAIN))
    return Frame(list(NAMES) + [data["response"]], vecs)
