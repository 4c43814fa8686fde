"""Migrating from H2O-3: load existing MOJO artifacts directly.

    JAX_PLATFORMS=cpu python examples/migrate_from_h2o3.py

A user arriving from the reference framework brings ``.zip`` MOJOs exported
by ``model.download_mojo()``. ``h2o.import_mojo`` reads them natively — GBM
and DRF tree bytecode, GLM, K-means, IsolationForest (+Extended),
StackedEnsemble archives with nested submodels, DeepLearning, PCA, GLRM,
CoxPH, Word2Vec, RuleFit, TargetEncoder, Isotonic, and XGBoost (the
embedded boosterBytes parsed natively) — so existing models score here
unchanged while retraining moves to the TPU-native builders.
"""
import os

import h2o3_tpu as h2o

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "data", "ref_mojo")


def main():
    # a REAL H2O-3 artifact: 50-tree bernoulli GBM trained on prostate
    model = h2o.import_mojo(os.path.join(FIXTURES,
                                         "gbm_variable_importance.zip"))
    print("imported:", model.output["source_algo"],
          "response:", model.response_column)

    fr = h2o.import_file(os.path.join(FIXTURES, "prostate.csv"))
    preds = model.predict(fr)
    print("scored", preds.nrows, "rows; columns:", preds.names)

    perf = model.model_performance(fr)
    print(f"AUC {float(perf.auc):.4f}  logloss {float(perf.logloss):.4f} "
          "(matches the metrics stored inside the artifact)")

    # nested ensembles work the same way
    ens = h2o.import_mojo(os.path.join(FIXTURES, "ensemble_binomial.zip"))
    print("ensemble:", ens.output["source_algo"],
          "bases:", [b.algo for b in ens.output["mojo"].base_models])

    # XGBoost MOJOs too: the xgboost binary model inside is parsed
    # natively (no xgboost install), reproducing the artifact's own
    # stored training MSE on its training data
    xgb = h2o.import_mojo(os.path.join(FIXTURES, "xgboost_prostate_age.zip"))
    xp = xgb.predict(fr).vec("predict").to_numpy()[: fr.nrows]
    age = fr.vec("AGE").to_numpy()[: fr.nrows]
    print(f"xgboost MOJO: train MSE {((xp - age) ** 2).mean():.6f} "
          "(artifact stores 3.323258)")


if __name__ == "__main__":
    main()
