"""AutoML leaderboard + stacked ensembles in a few lines.

    JAX_PLATFORMS=cpu python examples/automl_leaderboard.py
"""
import numpy as np

import h2o3_tpu as h2o
from h2o3_tpu.orchestration import AutoML


def main():
    rng = np.random.default_rng(1)
    n = 2_000
    X = rng.normal(size=(n, 5)).astype(np.float32)
    logit = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2] ** 2
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "pos", "neg")
    fr = h2o.Frame.from_arrays(
        {**{f"x{i}": X[:, i] for i in range(5)}, "y": y.astype(object)})

    aml = AutoML(max_models=4, nfolds=3, seed=1)
    aml.train(y="y", training_frame=fr)
    for row in aml.leaderboard.table()[1]:
        print(row[0], "auc=", row[1])
    print("leader:", aml.leaderboard.leader.key)


if __name__ == "__main__":
    main()
