"""Train, evaluate, and export a GBM end-to-end (the h2o-samples analog).

    JAX_PLATFORMS=cpu python examples/quickstart_gbm.py
"""
import numpy as np

import h2o3_tpu as h2o
from h2o3_tpu.models import GBM


def main():
    rng = np.random.default_rng(7)
    n = 5_000
    X = rng.normal(size=(n, 4)).astype(np.float32)
    city = rng.choice(["sfo", "nyc", "chi"], size=n).astype(object)
    logit = 1.2 * X[:, 0] - X[:, 1] + (city == "sfo")
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "yes", "no")
    fr = h2o.Frame.from_arrays(
        {"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2], "x3": X[:, 3],
         "city": city, "y": y.astype(object)})
    tr, te = fr.split_frame([0.8], seed=1)

    model = GBM(ntrees=50, max_depth=5, stopping_rounds=3, seed=1).train(
        y="y", training_frame=tr, validation_frame=te)
    mm = model.model_performance(te)
    print("holdout AUC:", round(mm.auc, 4), "logloss:", round(mm.logloss, 4))
    cols, rows = model.scoring_history
    print("scoring history rows:", len(rows))

    model.download_mojo("/tmp/quickstart.mojo")
    from h2o3_tpu.genmodel.mojo import MojoModel
    offline = MojoModel.load("/tmp/quickstart.mojo")
    p = offline.predict(te)
    print("offline predictions:", p.nrows, "rows;",
          "first:", p.vec("predict").labels()[0])


if __name__ == "__main__":
    main()
