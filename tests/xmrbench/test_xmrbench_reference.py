"""The copied reference against the program, the traffic it is fed, and
the control that the comparison has to fail."""

import numpy as np
import pytest

from xmrbench_testkit import REPO, tiny_root

from xmrbench import check, generator, weights
from xmrbench.control import control_readings
from xmrbench.references.xmr_tree import TreeReference

SPEC = {"model": "label_path", "targets": {"kind": "uniform"},
        "path_share": 0.5, "zipf_a": 1.3, "value_offset": 0.05}


@pytest.fixture(scope="module")
def tiny():
    levels = weights.tree_weights(np.random.SeedSequence(21), 3000, 5000, 32,
                                  [64, 64, 64], 0.8)
    queries = generator.label_path_queries(np.random.default_rng(22), levels,
                                           32, 300, 40, SPEC)
    return levels, queries


def test_reference_agrees_with_the_serving_engine(tiny):
    from repro.core import XMRTree
    from repro.serving import ServeConfig, XMRServingEngine
    from repro.sparse.csr import CSC, CSR

    levels, (qi, qx, qv) = tiny
    tree = XMRTree.from_weight_matrices(
        [CSC(ip, ix, dt, sh) for ip, ix, dt, sh in levels], 32)
    engine = XMRServingEngine(tree, ServeConfig(method="mscm_dense", beam=10,
                                                topk=10, max_batch=128))
    got_s, got_l = engine.serve_batch(CSR(qi, qx, qv, (300, 3000)))
    res = TreeReference(levels, 32).search(qi, qx, qv, np.arange(300),
                                           beam=10, topk=10)
    r = check.compare(got_s, got_l, res, 10)
    assert r["mismatched"] == 0 and r["checked"] >= 290
    assert r["score_rel_err"] < 1e-6


def test_queries_reach_into_the_tree(tiny):
    levels, (qi, qx, qv) = tiny
    # Every query holds exactly query_nnz distinct, sorted ids.
    assert (np.diff(qi) == 40).all()
    assert (np.diff(qx.reshape(300, 40), axis=1) > 0).all()
    ref = TreeReference(levels, 32)
    res = ref.search(qi, qx, qv, np.arange(300), beam=10, topk=10)
    nonzero = total = 0
    for li, lev in enumerate(ref.levels):
        cols = (res.visited[li][:, :, None] * 32 + np.arange(32)).reshape(
            300, -1)
        for q in range(300):
            feats = set(qx[qi[q]:qi[q + 1]].tolist())
            for c in cols[q][cols[q] < ref.n_cols[li]]:
                total += 1
                nonzero += bool(feats & set(lev.idx[c, : lev.nnz[c]].tolist()))
    # Most visited columns meet the query, so logits are not all 0 and
    # rankings are not decided by id tie-breaks.
    assert nonzero / total > 0.5


def test_search_is_blockwise_and_repeatable(tiny):
    levels, (qi, qx, qv) = tiny
    ref = TreeReference(levels, 32)
    a = ref.search(qi, qx, qv, np.arange(0, 300, 7), beam=10, topk=10,
                   block=5)
    b = ref.search(qi, qx, qv, np.arange(0, 300, 7), beam=10, topk=10,
                   block=64)
    assert (a.labels == b.labels).all() and (a.scores == b.scores).all()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_comparison(tmp_path, seed):
    # 1,024 answers compared, as many as a run of a real cell compares.
    root = tiny_root(tmp_path, pool=1024)
    low = control_readings("tiny.batch", seed, root=root)
    assert not low["correct"]
    assert low["score_rel_err"] > 1e-5
    same = control_readings("tiny.batch", seed, root=root, precision="f64")
    assert same["correct"] and same["score_rel_err"] == 0.0


def test_near_ties_at_a_cut_include_groups_that_span_it():
    from xmrbench.references.xmr_tree import near_tie_at_cut

    ss = np.array([
        [5, 4, 3, 2, 1],                    # clear cut
        [5, 4, 3, 3, 1],                    # equal scores span the cut
        [5, 4, 3, 3, 3 - 3e-6],             # ... and a near-tie below them
        [5, 3 + 3e-6, 3, 3, 1],             # ... and a near-tie above them
        [5, 4, 3, 3 - 3e-6, 1],             # a near-tie at the cut
        [5, 4, 3, -np.inf, -np.inf],        # masked candidates
    ])
    assert near_tie_at_cut(ss, 3).tolist() == [False, False, True, True,
                                               True, False]
    assert not near_tie_at_cut(ss, 5).any()
