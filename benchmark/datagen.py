"""A SMALL frame on the host, for the plain references, which run there."""

from __future__ import annotations

import numpy as np


def to_host(frame, response: str):
    """(X [rows, F] float32, y [rows] int) of ``frame``."""
    import jax
    feats = [n for n in frame.names if n != response]
    got = jax.device_get([frame.vec(n).data for n in frame.names])
    by = dict(zip(frame.names, got))
    X = np.stack([np.asarray(by[n])[: frame.nrows] for n in feats], axis=1)
    return X, np.asarray(by[response])[: frame.nrows].astype(np.int64)
