"""Share of the device's busy time inside the window that runs under the IRLS
step's scope ``gram`` (``jax.named_scope`` in ``glm._irls_step`` around
``_weighted_gram`` and ``_weighted_rhs``: the [rows, K] x [rows, K]
contraction X'WX at HIGHEST precision — six bf16 passes on the MXU — with the
row scaling by W fused into it, X'Wz, and the small assembly of the
[K+1, K+1] system), in percent. See ``_glm_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    return load("layer_metrics", "_glm_scopes").parts_share(r, ("gram",))
