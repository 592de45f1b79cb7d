"""Full substrate pipeline: cluster -> train -> sparsify -> serve -> P@k."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic_labeled_dataset
from repro.metrics import precision_at_k, recall_at_k
from repro.serving import ServeConfig, XMRServingEngine
from repro.trees import build_clustered_tree, build_tree_structure, pifa_embeddings
from repro.trees.train import train_xmr_model


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(7)
    ds = synthetic_labeled_dataset(
        rng, n_labels=128, d=256, n_train=768, n_test=192, query_nnz=14
    )
    model = train_xmr_model(
        ds.x_train, ds.y_train, ds.n_labels, branching=8, rng=rng, nnz_per_col=48,
        steps=120,
    )
    return ds, model


def test_tree_structure_shapes():
    t = build_tree_structure(100, 8)
    assert t.level_sizes == (8, 64, 512)
    assert (t.label_perm[:100] == np.arange(100)).all()
    assert (t.label_perm[100:] == -1).all()
    # ancestors nest properly
    leaf = np.arange(512)
    a1 = t.ancestor_at_level(leaf, 1)
    a0 = t.ancestor_at_level(leaf, 0)
    assert (a1 // 8 == a0).all()


def test_pifa_embeddings_normalized(rng):
    ds = synthetic_labeled_dataset(rng, n_labels=32, d=64, n_train=128, n_test=8)
    emb = pifa_embeddings(ds.x_train, ds.y_train, 32)
    norms = np.linalg.norm(emb, axis=1)
    assert ((norms < 1e-6) | (np.abs(norms - 1) < 1e-5)).all()


def test_clustering_groups_similar_labels(rng):
    ds = synthetic_labeled_dataset(
        rng, n_labels=64, d=128, n_train=512, n_test=8, n_groups=8
    )
    t = build_clustered_tree(ds.x_train, ds.y_train, 64, 8, rng)
    assert sorted(int(x) for x in t.label_perm if x >= 0) == list(range(64))


def test_trained_model_beats_chance(trained):
    ds, model = trained
    xi, xv = ds.x_test.to_ell(64)
    scores, labels = model.predict(jnp.asarray(xi), jnp.asarray(xv), beam=16, topk=5)
    p1 = precision_at_k(labels, ds.y_test, 1)
    r5 = recall_at_k(labels, ds.y_test, 5)
    assert p1 > 0.25          # chance is ~1/128
    assert r5 > p1 * 0.5
    assert scores.shape == labels.shape == (len(ds.y_test), 5)


def test_serving_engine_modes(trained):
    ds, model = trained
    eng = XMRServingEngine(
        model.tree,
        ServeConfig(beam=16, topk=5, ell_width=64),
        label_perm=model.structure.label_perm,
    )
    eng.warmup(ds.d, batch_sizes=(1, 64))
    s_b, l_b = eng.serve_batch(ds.x_test)
    s_o, l_o = eng.serve_online(ds.x_test, limit=16)
    np.testing.assert_array_equal(l_o, l_b[:16])
    np.testing.assert_allclose(s_o, s_b[:16], rtol=1e-5)
    summ = eng.latency_summary()
    assert summ["count"] > 0 and summ["p99_ms"] >= summ["p50_ms"]


def test_serving_methods_agree(trained):
    ds, model = trained
    outs = {}
    for method in ("vanilla", "mscm_dense", "mscm_pallas_grouped"):
        eng = XMRServingEngine(
            model.tree, ServeConfig(beam=16, topk=5, ell_width=64, method=method)
        )
        _, labels = eng.serve_batch(ds.x_test)
        outs[method] = labels
    base = outs["vanilla"]
    for m, l in outs.items():
        np.testing.assert_array_equal(l, base, err_msg=m)
