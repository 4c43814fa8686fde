"""Generator ``mnist_like``: MNIST's schema (and ``mnist8m``'s, the same 784
pixels at 8.1M rows), made ON the device.

784 float32 columns ``C1`` ... ``C784`` that hold whole numbers 0-255, the
28 x 28 grid row by row, and the categorical response ``C785`` with the
domain ``"0"`` ... ``"9"``, classes near uniform. No network here, so the
digits are not MNIST's: everything below is a CONSTANT of the generator,
written from memory of what the data set looks like (the configuration lists
it under ``assumed``), and the seed draws rows, never prototypes.

- Ten class prototypes (line segments with a Gaussian profile inside the
  central 20 x 20 box): three strokes EVERY class shares, 255 at the centre
  line, and one faint stroke of its own, ``MARK`` = 70 at the centre line,
  that tells the class. Under the row's shift, gain and noise a class is a
  faint mark somewhere beside bright strokes that say nothing: a network has
  to learn where to look, and one epoch over a million rows does not finish
  learning it (held-out error 23% after 4,000 updates of 32 rows and still
  falling; a generator whose classes were bright disjoint strokes was at its
  ceiling after 500, and no check could have told a build on half the rows
  from a whole one).
- A row is its class's prototype moved by a per-row shift of -2..2 pixels
  each way, scaled by a per-row gain, with noise on the lit pixels, clipped
  to 0-255 and rounded; the background stays exactly 0, so about four fifths
  of the pixels are 0, as in MNIST. One pixel in a hundred is "salt": a
  whole number 1-255 drawn uniformly, whatever the prototype says.
- ``CONSTANT_PIXELS``: a fixed set of 67 border pixels (the first and the
  last grid row, and the first column of grid rows 1-11) is 0 in every row:
  MNIST's training set has 67 such columns. Every other pixel is non-constant
  from a few thousand rows on (the salt), so the CPU rehearsal at 8,192 rows
  trains the same 717 inputs as the cell.
- ``FLIP``: that share of the labels is redrawn uniformly over the ten
  classes, so the ceiling is known: no model can have a held-out error under
  ``0.9 x FLIP`` or a log-loss under the entropy of a label given its true
  class, in expectation; ``ceiling`` gives both as the held-out rows at hand
  realise them (``ideal_score``'s role in the other generators).

``make(seed, fold, data)`` as in ``higgs_logit``: fold 0 the training frame,
1 a reference's sample, 2 held-out rows. ``data['domain_order'] ==
"reversed"`` writes the response with its domain, and so its codes, in
reverse order: the same rows, for a frame whose response has to be adapted to
the training layout by level name. The newest TRAINING frame is kept: the same
arguments again hand out the same resident Frame (a cell's checks ask for the
frame its builds trained on, and a copy would be 3 GB more on the device than
any build holds). ``pixels`` is the same rows as ONE uint8
matrix (the values are whole numbers 0-255, so nothing is lost): what a plain
reference reads, a quarter the size of the frame.
"""

from __future__ import annotations

import functools
import math

import numpy as np

SIDE = 28
FEATURES = SIDE * SIDE
CLASSES = 10
DOMAIN = tuple(str(k) for k in range(CLASSES))
NAMES = tuple(f"C{j + 1}" for j in range(FEATURES))

FLIP = 0.02            # share of the labels redrawn uniformly
SALT = 0.01            # share of the pixels replaced by a uniform 1-255
MAX_SHIFT = 2          # pixels, each way
GAIN = (0.35, 1.0)     # a row's brightness, uniform
NOISE_SD = 60.0        # on a lit pixel, in pixel units
STROKE_WIDTH = 0.9     # sd of a stroke's Gaussian profile, pixels
STROKE_FLOOR = 16.0    # a prototype under this is background: exactly 0
COMMON = 3             # strokes every class shares, 255 at the centre line
MARK = 70.0            # the one stroke that tells a class, at its centre line
STRIP = 112            # columns made a dispatch: four grid rows

#: the 67 pixels that are 0 in every row, as column indices 0..783
CONSTANT_PIXELS = tuple(sorted(
    set(range(SIDE)) | set(range(FEATURES - SIDE, FEATURES))
    | {r * SIDE for r in range(1, 12)}))


@functools.lru_cache(maxsize=1)
def prototypes() -> np.ndarray:
    """[10, 28, 28] float32, 0-255: the class prototypes."""
    rng = np.random.RandomState(20321)
    lo, hi = 4.5, SIDE - 4.5
    ends = rng.uniform(lo, hi, size=(COMMON + CLASSES, 2, 2))
    yy, xx = np.mgrid[0:SIDE, 0:SIDE].astype(np.float64)
    strokes = []
    for (y0, x0), (y1, x1) in ends:
        dy, dx = y1 - y0, x1 - x0
        t = ((yy - y0) * dy + (xx - x0) * dx) / max(dy * dy + dx * dx, 1e-9)
        t = np.clip(t, 0.0, 1.0)
        d2 = (yy - (y0 + t * dy)) ** 2 + (xx - (x0 + t * dx)) ** 2
        strokes.append(np.exp(-d2 / (2.0 * STROKE_WIDTH ** 2)))
    common = 255.0 * np.max(strokes[:COMMON], axis=0)
    out = np.stack([np.maximum(common, MARK * strokes[COMMON + k])
                    for k in range(CLASSES)])
    out[out < STROKE_FLOOR] = 0.0
    return out.astype(np.float32)


@functools.lru_cache(maxsize=1)
def shifted_table() -> np.ndarray:
    """[10 x 25, 784] float32: every prototype under every shift (moved with
    zero fill), the row of class ``k`` under shift ``s`` at ``k * 25 + s``."""
    protos = prototypes()
    side = 2 * MAX_SHIFT + 1
    table = np.zeros((CLASSES * side * side, FEATURES), np.float32)
    for k in range(CLASSES):
        padded = np.pad(protos[k], MAX_SHIFT)
        for s in range(side * side):
            dy, dx = divmod(s, side)
            table[k * side * side + s] = padded[
                dy:dy + SIDE, dx:dx + SIDE].reshape(-1)
    return table


def least_error() -> float:
    """Expected held-out error of a model that knows every row's true class."""
    return FLIP * (CLASSES - 1) / CLASSES


def least_logloss() -> float:
    """Expected held-out log-loss of that model: the entropy of a label
    given its true class."""
    own = 1.0 - FLIP + FLIP / CLASSES
    other = FLIP / CLASSES
    return -(own * math.log(own) + (CLASSES - 1) * other * math.log(other))


def _row_draws(key, plen: int):
    """(true class, label, shift index, gain) of every row, [plen] each."""
    import jax
    import jax.numpy as jnp
    kc, ks, kg, kf, kr = jax.random.split(key, 5)
    true = jax.random.randint(kc, (plen,), 0, CLASSES)
    shifts = (2 * MAX_SHIFT + 1) ** 2
    shift = jax.random.randint(ks, (plen,), 0, shifts)
    gain = jax.random.uniform(kg, (plen,), jnp.float32, *GAIN)
    redrawn = jax.random.uniform(kf, (plen,)) < FLIP
    label = jnp.where(redrawn, jax.random.randint(kr, (plen,), 0, CLASSES),
                      true)
    return true, label, shift, gain


def _strip_values(key, strip, true, shift, gain, table):
    """[plen, STRIP] float32 whole numbers 0-255: columns ``strip * STRIP``
    onward of every row."""
    import jax
    import jax.numpy as jnp
    plen = true.shape[0]
    first = strip * STRIP
    shifts = (2 * MAX_SHIFT + 1) ** 2
    part = jax.lax.dynamic_slice_in_dim(table, first, STRIP, axis=1)
    base = jnp.take(part, true * shifts + shift, axis=0)
    kn, ks, kv = jax.random.split(jax.random.fold_in(key, 1000 + strip), 3)
    noise = jax.random.normal(kn, (plen, STRIP), jnp.float32)
    value = jnp.where(base > 0, gain[:, None] * base + NOISE_SD * noise, 0.0)
    salted = jax.random.uniform(ks, (plen, STRIP)) < SALT
    salt = 1.0 + jnp.floor(255.0 * jax.random.uniform(kv, (plen, STRIP)))
    value = jnp.where(salted, salt, value)
    constant = jax.lax.dynamic_slice_in_dim(
        jnp.asarray(np.isin(np.arange(FEATURES), CONSTANT_PIXELS)), first, STRIP)
    value = jnp.where(constant[None, :], 0.0, value)
    return jnp.clip(jnp.round(value), 0.0, 255.0)


@functools.lru_cache(maxsize=None)
def _programs(plen: int):
    """The three jitted pieces at one padded length: the row draws, a strip
    as ``STRIP`` float32 columns (padding rows NaN), a strip as one uint8
    block."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.parallel.mesh import row_sharding
    rows1, rows2 = row_sharding(1), row_sharding(2)

    def columns(key, strip, rows, true, shift, gain, table):
        v = _strip_values(key, strip, true, shift, gain, table)
        v = jnp.where((jnp.arange(plen) < rows)[:, None], v, jnp.nan)
        return tuple(v[:, j] for j in range(STRIP))

    def block(key, strip, true, shift, gain, table):
        return _strip_values(key, strip, true, shift, gain,
                             table).astype(jnp.uint8)

    return (jax.jit(functools.partial(_row_draws, plen=plen),
                    out_shardings=(rows1,) * 4),
            jax.jit(columns, out_shardings=(rows1,) * STRIP),
            jax.jit(block, out_shardings=rows2))


#: the newest training frame, by make's arguments
_RESIDENT: dict = {}


def _key(seed: int, fold: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed), fold)


def make(seed: int, fold: int, data: dict):
    """A Frame of ``data['rows']`` rows: ``C1`` ... ``C784`` and the
    categorical response ``data['response']``."""
    import jax.numpy as jnp

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.types import CAT_NA, VecType
    from h2o3_tpu.frame.vec import Vec, padded_len

    rows = int(data["rows"])
    asked = (seed, fold, rows, data["response"], data.get("domain_order"))
    if asked in _RESIDENT:
        return _RESIDENT[asked]
    plen = padded_len(rows)
    draws, columns, _ = _programs(plen)
    key = _key(seed, fold)
    true, label, shift, gain = draws(key)
    table = jnp.asarray(shifted_table())
    vecs = []
    for strip in range(FEATURES // STRIP):
        vecs += [Vec.from_device(c, rows) for c in columns(
            key, jnp.int32(strip), jnp.int32(rows), true, shift, gain, table)]
    domain = DOMAIN
    if data.get("domain_order") == "reversed":
        domain, label = DOMAIN[::-1], CLASSES - 1 - label
    label = jnp.where(jnp.arange(plen) < rows, label, CAT_NA).astype(jnp.int32)
    vecs.append(Vec.from_device(label, rows, VecType.CAT, domain=domain))
    frame = Frame(list(NAMES) + [data["response"]], vecs)
    if fold == 0:
        _RESIDENT.clear()
        _RESIDENT[asked] = frame
    return frame


def pixels(seed: int, fold: int, rows: int):
    """(the rows ``make`` writes as one uint8 [rows, 784] matrix, their labels
    [rows] int32, their TRUE classes [rows] int32), on the device. Labels are
    the class numbers, whatever order a frame writes its domain in."""
    import jax.numpy as jnp

    from h2o3_tpu.frame.vec import padded_len
    plen = padded_len(rows)
    draws, _, block = _programs(plen)
    key = _key(seed, fold)
    true, label, shift, gain = draws(key)
    table = jnp.asarray(shifted_table())
    out = jnp.concatenate(
        [block(key, jnp.int32(strip), true, shift, gain, table)
         for strip in range(FEATURES // STRIP)], axis=1)
    return out[:rows], label[:rows], true[:rows]


def ceiling(label, true) -> tuple[float, float]:
    """(error, log-loss) on THESE rows of the model that knows every row's
    true class and the share of labels redrawn: what no model may beat but by
    the luck of a sample."""
    same = np.asarray(label) == np.asarray(true)
    own = 1.0 - FLIP + FLIP / CLASSES
    return (float(1.0 - same.mean()),
            float(-np.where(same, math.log(own),
                            math.log(FLIP / CLASSES)).mean()))
