"""Blocking fetches per IRLS iteration over the window:
``h2o3_glm_megasteps_total`` (one ``device_get`` a megastep) over
``h2o3_glm_iterations_total``. 1/K when every megastep runs its K iterations
(``H2O3TPU_MEGASTEP_K``, 4); 0.4 for a fit of 5 iterations (4 + 1)."""

LAYER, UNIT, MOVES = "builder", "count", "train_work_per_s_chip"
DRIVERS = ("build_loop",)


def read(r):
    from benchmark import counters
    its = counters.delta(r.before, r.after, "h2o3_glm_iterations_total")
    if its <= 0:
        return None
    return counters.delta(r.before, r.after, "h2o3_glm_megasteps_total") / its
