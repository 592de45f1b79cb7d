"""Method facts live in ``core.tree``: the names, the choice ``"auto"``
makes, and the refusal of any other name before anything is traced.

The three names below were methods once (a binary-search MSCM, a fused
Pallas kernel with an in-kernel gather, and a pre-gathered one); every
entry point must now refuse them, not fail inside a jit trace or never.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS, XMRTree
from repro.core.tree import resolve_method
from repro.index import ScatterGatherPlanner, partition_tree
from repro.serving import ServeConfig, XMRServingEngine
from repro.serving.config import QuantConfig
from repro.sparse import random_sparse_csr
from tests.conftest import make_tree_weights

REMOVED = tuple(
    f"mscm_{name}" for name in ("searchsorted", "pallas", "pallas_pregather")
)


@pytest.fixture(scope="module")
def tree_and_queries():
    rng = np.random.default_rng(7)
    d, B = 60, 4
    tree = XMRTree.from_weight_matrices(
        make_tree_weights(rng, d, [4, 16, 64], B), B
    )
    xi, xv = map(jnp.asarray, random_sparse_csr(3, d, 8, rng).to_ell())
    return tree, xi, xv


def _tree_infer(tree_and_queries, method):
    tree, xi, xv = tree_and_queries
    tree.infer(xi, xv, beam=2, topk=2, method=method)


def _planner(tree_and_queries, method):
    ScatterGatherPlanner(partition_tree(tree_and_queries[0], 2), method=method)


def _engine(tree_and_queries, method):
    XMRServingEngine(tree_and_queries[0], ServeConfig(method=method))


@pytest.mark.parametrize("method", REMOVED)
@pytest.mark.parametrize(
    "entry", [_tree_infer, _planner, _engine],
    ids=["XMRTree.infer", "ScatterGatherPlanner", "XMRServingEngine"],
)
def test_removed_method_refused(tree_and_queries, entry, method):
    assert len(METHODS) == 4 and method not in METHODS
    with pytest.raises(ValueError, match="choose from"):
        entry(tree_and_queries, method)


@pytest.mark.parametrize(
    "backend, tier, want",
    [("tpu", "exact", "mscm_pallas_grouped"),
     ("cpu", "exact", "mscm_dense"),
     ("cpu", "int8", "mscm_pallas_grouped_q")],
    ids=["auto-tpu", "auto-cpu", "quant-tier"],
)
def test_resolve_method(monkeypatch, backend, tier, want):
    """``"auto"`` serves the grouped kernel on a TPU and the dense lookup
    elsewhere; a compressed tier forces the quantized grouped kernel, and
    an exact method named beside it is refused."""
    QuantConfig(tier=tier)  # the tier is one the config accepts
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_method("auto", tier) == want
    if tier == "exact":
        assert resolve_method(want, tier) == want
    else:
        with pytest.raises(ValueError, match="serves via"):
            resolve_method("mscm_dense", tier)
