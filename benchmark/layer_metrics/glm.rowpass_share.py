"""Share of the device's busy time inside the window that runs under the IRLS
step's scopes ``eta``, ``weights`` and ``deviance``: the passes over the rows
that are not the Gram (X·beta at HIGHEST precision, then mu, W and z, then the
deviance's sum), bound by the read of X, in percent. One metric for the
three because a fusion carries one scope, its root's, and where XLA cuts
these passes is its own choice. See ``_glm_scopes.py``."""

from benchmark.plugins import load

LAYER, UNIT, MOVES = "program", "%", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    glm = load("layer_metrics", "_glm_scopes")
    return glm.parts_share(r, glm.ROW_PASSES)
