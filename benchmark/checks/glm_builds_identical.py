"""One more ``train()`` after the window — same rows (the frame made again
from the seed), same parameters — returns the window's last model bit for
bit: every standardised coefficient, and the same iteration count.
``build_loop`` keeps only the newest model and its fingerprint is of trees,
so the comparison needs the extra build."""


def check(ctx) -> dict:
    import jax
    import numpy as np

    from benchmark.plugins import load
    from h2o3_tpu.utils.registry import DKV
    frame = load("checks", "_glm").training_frame(ctx)
    again = ctx.builder(**ctx.config["params"]).train(
        y=ctx.data["response"], training_frame=frame)
    a, b = (np.asarray(jax.device_get(m.output["beta"]))
            for m in (ctx.model, again))
    same = (a.shape == b.shape and a.tobytes() == b.tobytes()
            and ctx.model.output["iterations"] == again.output["iterations"])
    DKV.remove(again.key)
    return {"ok": bool(same), "coefficients": int(a.size),
            "iterations": [int(ctx.model.output["iterations"]),
                           int(again.output["iterations"])],
            "max_abs_diff": float(np.max(np.abs(a - b))) if a.shape == b.shape
            else None}
