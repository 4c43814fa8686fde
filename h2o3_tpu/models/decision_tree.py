"""Single Decision Tree (SDT).

Reference: ``hex/tree/dt/DT.java`` — one CART grown level-wise with
entropy-based binary splits, binomial or regression response. Here the shared
level-synchronous histogram engine grows the tree in one shot: with zero prior
score the second-order leaf objective reduces to the weighted node mean, so a
single "boosting" step with identity gradients IS the CART fit (leaf = mean
response; for a 0/1 response that mean is the class-1 probability).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import SharedTreeBuilder, SharedTreeModel, tree_matrix
from h2o3_tpu.models.job import Job
from h2o3_tpu.models.model_base import make_model_key
from h2o3_tpu.models.tree import TreeParams, grow_trees_batched
from h2o3_tpu.models.data_info import response_as_float


class DecisionTreeModel(SharedTreeModel):
    algo = "decisiontree"

    def _score_raw(self, frame: Frame):
        raw = self._tree_raw_sum(frame)
        if self.nclasses == 2:
            p = jnp.clip(raw, 0.0, 1.0)
            return jnp.stack([1 - p, p], axis=1)
        return raw


class DecisionTree(SharedTreeBuilder):
    """h2o-py surface: ``H2ODecisionTreeEstimator`` (algo ``dt``)."""

    algo = "decisiontree"

    @classmethod
    def defaults(cls) -> dict:
        d = super().defaults()
        d.update(max_depth=10, min_rows=10.0, nbins=64, ntrees=1)
        return d

    def _fit(self, job: Job, frame: Frame, x, y, weights) -> DecisionTreeModel:
        p = self.params
        yvec = frame.vec(y)
        if yvec.is_categorical and yvec.cardinality() != 2:
            raise ValueError("DecisionTree supports binary or numeric responses")
        X, edges, binned, yy, valid, yvec, domains = self._prepare(frame, x, y, weights)
        w = weights * valid
        yy = jnp.where(w > 0, yy, 0.0)

        tp = TreeParams(max_depth=int(p["max_depth"]), nbins=self._n_bins,
                        min_rows=float(p["min_rows"]), reg_lambda=0.0,
                        min_split_improvement=float(p["min_split_improvement"]))
        # identity-gradient trick: g = -w*y, h = w ⇒ leaf = Σwy/Σw (node mean)
        g = -w * yy
        h = w
        key = jax.random.PRNGKey(int(p.get("seed") or 0) or 5)
        trees, _ = grow_trees_batched(binned, edges, g[None], h[None], w[None],
                                      tp, jnp.ones(binned.shape[1], bool),
                                      key=key, cat_feats=self._cat_feats,
                                      bins_used=self._bins_used)
        job.update(1.0, "tree grown")

        return DecisionTreeModel(
            key=make_model_key(self.algo, self.model_id),
            params=self.params, data_info=None, response_column=y,
            response_domain=yvec.domain if yvec.is_categorical else None,
            output=dict(trees=trees, x_cols=list(x), feat_domains=domains,
                        **self._cat_output()),
        )
