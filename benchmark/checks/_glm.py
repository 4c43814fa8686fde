"""What the three ``glm_*`` checks share: the training frame made again from
the seed (the generator is deterministic, so these are the timed builds' own
rows; ``build_loop`` does not hand its frame on), and the float64 reference's
fit of it, each made once a run and kept on the driver's context; and the
program's one departure from the reference, its ridge, worked in float64."""

from __future__ import annotations

#: ``glm._irls_step``'s ridge on the diagonal of every step's Gram, the
#: intercept's too: JITTER x (the Gram's mean diagonal + 1). Stated here, not
#: imported: the checks share no code with the program
JITTER = 1e-5


def programs_ridge(ref, design, y, start, steps: int = 4):
    """The fixed point of IRLS with the program's ridge, in float64, from
    ``start`` (the reference's jitter-free fit, 1e-2 away: Newton steps, so
    four are more than enough). What the program would return in exact
    arithmetic; the plain reference stays jitter-free. Returns the
    coefficients and the ridge ``j`` at them."""
    import numpy as np
    import scipy.linalg
    beta, j = np.array(start, np.float64), 0.0
    for _ in range(steps):
        gram, rhs, _dev = ref.normal_equations(design.X, y, beta)
        j = JITTER * (np.trace(gram) / len(gram) + 1.0)
        beta = scipy.linalg.cho_solve(
            scipy.linalg.cho_factor(gram + j * np.eye(len(gram))), rhs)
    return beta, j


def generator(ctx):
    from benchmark import plugins
    return plugins.load("generators", ctx.data["generator"])


def training_frame(ctx):
    if getattr(ctx, "glm_frame", None) is None:
        ctx.glm_frame = generator(ctx).make(ctx.cell.seed, 0, ctx.data)
    return ctx.glm_frame


def reference(ctx):
    """(module, Design, domains, y, Fit) of the configuration's plain
    reference on the whole training frame, under the builder's own stopping
    parameters."""
    if getattr(ctx, "glm_reference", None) is None:
        from benchmark import plugins
        ref = plugins.load("reference", ctx.config["reference"])
        params = ctx.params
        if float(params["lambda_"]) != 0.0 or params["family"] != "binomial":
            raise RuntimeError("glm_irls_numpy is a lambda-0 binomial reference")
        design, domains, y = ref.from_frame(
            training_frame(ctx), ctx.data["response"],
            standardize=bool(params["standardize"]))
        fit = ref.fit(design, y, int(params["max_iterations"]),
                      float(params["beta_epsilon"]),
                      float(params["objective_epsilon"]))
        ctx.glm_reference = (ref, design, domains, y, fit)
    return ctx.glm_reference
