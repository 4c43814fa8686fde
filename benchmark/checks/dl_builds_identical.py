"""One more ``train()`` after the window — same rows (the generator hands
the resident training frame out again), same parameters, same ``seed`` —
returns the window's last model bit for bit: every weight and bias array.
``build_loop`` keeps only the newest model and its fingerprint is of trees,
so the comparison needs the extra build (``glm_builds_identical`` is the
pattern)."""


def check(ctx) -> dict:
    import jax
    import numpy as np

    from benchmark.plugins import load
    from h2o3_tpu.utils.registry import DKV
    frame = load("checks", "_dl").training_frame(ctx)
    again = ctx.builder(**ctx.config["params"]).train(
        y=ctx.data["response"], training_frame=frame)
    a, b = (jax.device_get(jax.tree.leaves(m.output["params"]))
            for m in (ctx.model, again))
    same = len(a) == len(b) and all(
        x.shape == y.shape and x.tobytes() == y.tobytes() for x, y in zip(a, b))
    worst = max((float(np.max(np.abs(x - y))) for x, y in zip(a, b)
                 if x.shape == y.shape), default=None)
    DKV.remove(again.key)
    return {"ok": bool(same), "arrays": len(a),
            "parameters": int(sum(x.size for x in a)),
            "samples_trained": [float(ctx.model.output["samples_trained"]),
                                float(again.output["samples_trained"])],
            "max_abs_diff": worst}
