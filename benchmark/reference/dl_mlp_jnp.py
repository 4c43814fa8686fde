"""Plain reference of the dense network H2O-3's tuning guide trains on MNIST
(``h2o.deeplearning(activation = "RectifierWithDropout", hidden = c(1024,
1024, 2048), l1 = 1e-5, input_dropout_ratio = 0.2)``, ADADELTA, cross-entropy):
forward pass, loss, a backward pass written out BY HAND, ``l1`` and the
ADADELTA update, in straightforward ``jax.numpy``, float32, every product
under ``jax.default_matmul_precision("highest")``. It shares nothing with
``h2o3_tpu/models/deeplearning.py``: no ``jax.grad`` of anything, no scan
over epochs, no ``accounted_jit``, no ``DataInfo``. It takes the raw pixel
columns as they are, finds the non-constant ones itself and works their
means and standard deviations in float64 (``standardize``).

One update, for a minibatch ``x`` of ``B`` standardised rows with classes
``y`` and row weights ``w`` (1 a row; 0 for a frame's padding rows), keep
shares ``k_0`` (input, 0.8) and ``k_i`` (hidden, 0.5), ``theta`` all ``W_i``,
``b_i``:

    h_0 = m_0 * x / k_0
    z_i = h_{i-1} W_i + b_i,  a_i = max(z_i, 0),  h_i = m_i * a_i / k_i
    out = h_n W_{n+1} + b_{n+1}
    loss = sum_r w_r * -log softmax(out_r)[y_r] / sum_r w_r
    g = d loss / d theta + l1 * sign(theta)
    E_g <- rho E_g + (1 - rho) g^2
    D = -sqrt(E_D + eps) / sqrt(E_g + eps) * g
    E_D <- rho E_D + (1 - rho) D^2
    theta <- theta + D

THE RANDOM STREAM IS NOT THE MATHEMATICS UNDER TEST: the permutation that
picks a minibatch's rows and the keep-masks ``m_i`` are INPUTS, arrays the
caller draws from the same keys the program uses (a check or a test does
that) and hands over.

Departures from the reference implementation (``hex/deeplearning/
Neurons.java``), all three the documented design of the builder this
reference is held against (its module docstring; SURVEY.md section 7, step
7), stated here rather than hidden:

1. synchronous minibatch updates (the gradient averaged over ``B`` rows, one
   update a minibatch) in place of Hogwild row-at-a-time updates with
   per-iteration model averaging;
2. inverted dropout (kept units scaled by ``1 / k`` at training time) in
   place of weights halved at scoring time: equal in expectation;
3. ``l1`` on the biases too;
4. the derivative of ``max(z, 0)`` at exactly ``z == 0`` is taken as 1/2
   (the subgradient ``jax.numpy.maximum`` takes, and so the builder) where
   ``Neurons.java`` takes 0. It matters only for a row whose whole input to a
   layer is zero (every unit below it dead or dropped) while the bias is
   still 0: one row in a hundred at a toy network's 16 units, none at 1,024.

Rectifier hidden layers and a cross-entropy output only: the configuration's.
A caller may wrap
``update`` in ``jax.jit`` and loop it (31,250 updates dispatched operation by
operation would take minutes); the mathematics stays what is written here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


# -- data --------------------------------------------------------------------

def standardize(pixels, block: int = 65536) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(indices of the non-constant columns, their means, their standard
    deviations) of raw ``pixels`` [rows, columns], in float64 on the host, a
    block of rows at a time (a million rows are 6 GB as float64); the
    deviation is the sample's (n - 1), as H2O's roll-ups have it. Sums and
    sums of squares of whole numbers 0-255 are exact in float64 far past a
    million rows, so the one-pass variance loses nothing here."""
    rows, cols = pixels.shape
    lo, hi = np.full(cols, np.inf), np.full(cols, -np.inf)
    s, ss = np.zeros(cols), np.zeros(cols)
    for start in range(0, rows, block):
        a = np.asarray(pixels[start:start + block]).astype(np.float64)
        lo, hi = np.minimum(lo, a.min(axis=0)), np.maximum(hi, a.max(axis=0))
        s += a.sum(axis=0)
        ss += (a * a).sum(axis=0)
    kept = np.flatnonzero(lo != hi)
    var = (ss - s * s / rows) / (rows - 1)
    return kept, (s / rows)[kept], np.sqrt(var[kept])


def design(pixels, kept, mean, sd):
    """Raw rows [B, columns] -> standardised float32 inputs [B, kept]."""
    x = jnp.asarray(pixels, jnp.float32)[:, jnp.asarray(kept)]
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(sd, jnp.float32)


# -- the network -------------------------------------------------------------

def zeros_like(theta) -> dict:
    """ADADELTA's state for ``theta``: E_g and E_D, all zero."""
    return {"Eg": jax.tree.map(jnp.zeros_like, theta),
            "Ed": jax.tree.map(jnp.zeros_like, theta)}


def forward(theta, x, masks=None, keep=()):
    """Logits [B, classes]; with ``masks`` (one [B, width] boolean array a
    dropout site, input first; ``keep`` their keep shares) the training-time
    pass, and then also what the backward pass needs."""
    with jax.default_matmul_precision(HIGHEST):
        n = len(theta["W"]) - 1
        h = x if masks is None else masks[0] * x / keep[0]
        hs, zs = [h], []
        for i in range(n):
            z = h @ theta["W"][i] + theta["b"][i]
            a = jnp.maximum(z, 0.0)
            h = a if masks is None else masks[i + 1] * a / keep[i + 1]
            zs.append(z)
            hs.append(h)
        out = h @ theta["W"][n] + theta["b"][n]
    return out, hs, zs


def log_softmax(out):
    shifted = out - out.max(axis=1, keepdims=True)
    return shifted - jnp.log(jnp.exp(shifted).sum(axis=1, keepdims=True))


def predict_proba(theta, x):
    """Class probabilities [B, classes] of standardised rows: no dropout, no
    rescale (the dropout was inverted)."""
    return jnp.exp(log_softmax(forward(theta, x)[0]))


def loss_and_gradient(theta, x, y, w, masks, keep):
    """The weighted mean cross-entropy of the minibatch and its gradient by
    every ``W_i`` and ``b_i``, the backward pass written out."""
    out, hs, zs = forward(theta, x, masks, keep)
    logp = log_softmax(out)
    onehot = (y[:, None] == jnp.arange(out.shape[1])[None, :]).astype(jnp.float32)
    wsum = jnp.maximum(w.sum(), 1e-8)
    loss = -(w * (onehot * logp).sum(axis=1)).sum() / wsum
    n = len(theta["W"]) - 1
    gW, gb = [None] * (n + 1), [None] * (n + 1)
    with jax.default_matmul_precision(HIGHEST):
        d = (w / wsum)[:, None] * (jnp.exp(logp) - onehot)      # d loss / d out
        for i in range(n, -1, -1):
            gW[i] = hs[i].T @ d
            gb[i] = d.sum(axis=0)
            if i:
                dh = d @ theta["W"][i].T                        # by h_i
                da = dh * masks[i] / keep[i]                    # by a_i
                z = zs[i - 1]
                d = da * ((z > 0) + 0.5 * (z == 0))             # by z_i
    return loss, {"W": gW, "b": gb}


def update(theta, state, x, y, w, masks, *, keep, l1, rho, eps):
    """One ADADELTA update; returns (theta, state, the minibatch's loss)."""
    loss, g = loss_and_gradient(theta, x, y, w, masks, keep)
    g = jax.tree.map(lambda gi, t: gi + l1 * jnp.sign(t), g, theta)
    Eg = jax.tree.map(lambda e, gi: rho * e + (1.0 - rho) * gi * gi,
                      state["Eg"], g)
    delta = jax.tree.map(
        lambda ed, eg, gi: -jnp.sqrt(ed + eps) / jnp.sqrt(eg + eps) * gi,
        state["Ed"], Eg, g)
    Ed = jax.tree.map(lambda e, d: rho * e + (1.0 - rho) * d * d,
                      state["Ed"], delta)
    return jax.tree.map(jnp.add, theta, delta), {"Eg": Eg, "Ed": Ed}, loss


# -- scores ------------------------------------------------------------------

def logloss_and_error(proba, y) -> tuple[float, float]:
    """Mean -log p[y] (p clipped to 1e-15, as H2O's metric does) and the
    share of rows whose most probable class is not ``y``, in float64."""
    p = np.asarray(proba, np.float64)
    y = np.asarray(y).astype(np.int64)
    own = np.clip(p[np.arange(len(y)), y], 1e-15, 1.0)
    return float(-np.log(own).mean()), float((p.argmax(axis=1) != y).mean())
