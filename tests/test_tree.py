"""End-to-end tree inference: Algorithm 1 with every masked-matmul method.

Pins the paper's exactness claim at the system level under the contract
stated above ``core.tree.METHODS``: vanilla, dense-lookup MSCM and the
grouped Pallas kernel return identical labels, and scores that agree to a
few f32 ulp (each method sums the chunk dot products in its own order).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS, XMRTree
from repro.core.mscm import scatter_dense
from repro.core.tree import level_combined
from repro.sparse import random_sparse_csr
from tests.conftest import (
    assert_cross_method_close,
    brute_force_scores,
    make_tree_weights,
)


@pytest.fixture
def small_tree(rng):
    d, B = 150, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    x = random_sparse_csr(12, d, 18, rng)
    xi, xv = x.to_ell()
    return tree, ws, x, jnp.asarray(xi), jnp.asarray(xv)


def test_full_beam_equals_brute_force(small_tree):
    tree, ws, x, xi, xv = small_tree
    ref = brute_force_scores(x.to_dense(), ws)
    ref_top = np.argsort(-ref, axis=1, kind="stable")[:, :5]
    ref_s = np.take_along_axis(ref, ref_top, axis=1)
    s, l = tree.infer(xi, xv, beam=512, topk=5)  # beam == L => exact search
    np.testing.assert_array_equal(np.asarray(l), ref_top)
    np.testing.assert_allclose(np.asarray(s), ref_s, rtol=1e-5, atol=1e-6)


# The quantized tier's method (suffix ``_q``) is the documented exception
# to the exact-parity claim: it needs a QuantizedTree and its contract
# (bitwise vs the exact grouped kernel on dequantized weights) lives in
# tests/test_quant.py.
@pytest.mark.parametrize(
    "method", [m for m in METHODS if not m.endswith("_q")]
)
def test_methods_identical(small_tree, method):
    """The paper's 'free of charge' claim: every exact method, same results."""
    tree, ws, x, xi, xv = small_tree
    s0, l0 = tree.infer(xi, xv, beam=10, topk=5, method="vanilla")
    s, l = tree.infer(xi, xv, beam=10, topk=5, method=method)
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l0))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0), rtol=1e-5, atol=1e-6)


def test_log_space_ranking_matches_prod(small_tree):
    tree, ws, x, xi, xv = small_tree
    s_p, l_p = tree.infer(xi, xv, beam=10, topk=5, score_mode="prod")
    s_l, l_l = tree.infer(xi, xv, beam=10, topk=5, score_mode="logsum")
    np.testing.assert_array_equal(np.asarray(l_p), np.asarray(l_l))
    np.testing.assert_allclose(np.exp(np.asarray(s_l)), np.asarray(s_p), rtol=1e-4)


def test_beam_widening_converges_to_exact(small_tree):
    """P@1 under beam search increases to exact-search P@1 as b grows."""
    tree, ws, x, xi, xv = small_tree
    ref = brute_force_scores(x.to_dense(), ws)
    exact_top1 = np.argmax(ref, axis=1)
    hits = []
    for b in (1, 4, 32, 512):
        _, l = tree.infer(xi, xv, beam=b, topk=1)
        hits.append((np.asarray(l)[:, 0] == exact_top1).mean())
    assert hits[-1] == 1.0
    assert all(hits[i] <= hits[i + 1] + 1e-9 for i in range(len(hits) - 1))


def test_online_single_query(small_tree):
    """Online setting (n=1) — the paper's second serving mode."""
    tree, ws, x, xi, xv = small_tree
    s_b, l_b = tree.infer(xi, xv, beam=10, topk=5)
    for i in range(3):
        s1, l1 = tree.infer(xi[i : i + 1], xv[i : i + 1], beam=10, topk=5)
        np.testing.assert_array_equal(np.asarray(l1)[0], np.asarray(l_b)[i])
        np.testing.assert_allclose(np.asarray(s1)[0], np.asarray(s_b)[i], rtol=1e-5)


@pytest.mark.parametrize("beam", [1, 4, 10])
@pytest.mark.parametrize("qt", [4, 8])
def test_grouped_bitwise_parity(small_tree, beam, qt):
    """The device-grouped MXU path matches dense-lookup MSCM end-to-end —
    same labels, scores to a few ulp — across beam widths and query-tile
    heights (ragged last tiles included); and it is bitwise stable across
    batch sizes (one query served alone vs inside the batch)."""
    tree, ws, x, xi, xv = small_tree
    s0, l0 = tree.infer(xi, xv, beam=beam, topk=5, method="mscm_dense")
    s1, l1 = tree.infer(xi, xv, beam=beam, topk=5,
                        method="mscm_pallas_grouped", qt=qt)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0))
    assert_cross_method_close(s1, s0)
    s2, l2 = tree.infer(xi[:3], xv[:3], beam=beam, topk=5,
                        method="mscm_pallas_grouped", qt=qt)
    np.testing.assert_array_equal(np.asarray(l2), np.asarray(l1)[:3])
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1)[:3])


def test_grouped_bitwise_parity_logsum(small_tree):
    tree, ws, x, xi, xv = small_tree
    s0, l0 = tree.infer(xi, xv, beam=10, topk=5, method="mscm_dense",
                        score_mode="logsum")
    s1, l1 = tree.infer(xi, xv, beam=10, topk=5,
                        method="mscm_pallas_grouped", score_mode="logsum")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0))
    assert_cross_method_close(s1, s0)


def test_grouped_ragged_and_padded_chunks(rng):
    """L not divisible by B (padded chunks) + beam not divisible by qt
    (ragged last tile per chunk): grouped matches dense (same labels,
    scores to a few ulp), phantoms never surface."""
    from repro.sparse import random_sparse_csc

    d, B = 80, 8
    ws = [random_sparse_csc(d, 6, 8, rng), random_sparse_csc(d, 42, 8, rng)]
    tree = XMRTree.from_weight_matrices(ws, [6, 8])
    x = random_sparse_csr(20, d, 12, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    s0, l0 = tree.infer(xi, xv, beam=5, topk=7, method="mscm_dense")
    s1, l1 = tree.infer(xi, xv, beam=5, topk=7,
                        method="mscm_pallas_grouped", qt=4)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0))
    assert_cross_method_close(s1, s0)
    assert np.asarray(l1).max() < 42


def test_grouped_fully_jitted(small_tree):
    """The grouped method compiles as ONE XLA program: tracing succeeds (a
    host-side grouping step would raise a TracerArrayConversionError), the
    jaxpr contains no host callbacks, and repeated same-shape calls reuse
    the compiled executable."""
    import jax

    from repro.core.tree import _tree_infer

    tree, ws, x, xi, xv = small_tree

    def run(a, b):
        return _tree_infer(
            tuple(tree.layers), tree.n_cols, tree.branching, tree.d, a, b,
            beam=4, topk=3, method="mscm_pallas_grouped",
            score_mode="prod", qt=4,
        )

    jaxpr = jax.make_jaxpr(run)(xi, xv)
    assert "callback" not in str(jaxpr), "grouped path must not leave the jit"

    if hasattr(_tree_infer, "_cache_size"):
        run(xi, xv)
        size_after_first = _tree_infer._cache_size()
        run(xi, xv)  # same shapes/statics -> no recompile
        assert _tree_infer._cache_size() == size_after_first


@pytest.mark.parametrize(
    "method", [m for m in METHODS if not m.endswith("_q")]
)
def test_grouped_program_holds_no_dense_table(small_tree, method):
    """The lowered ``_tree_infer`` holds the f32 [n, d+1] query table, and
    the scatter that fills it, exactly where ``reads_dense_table`` says so:
    the grouped program builds its query tiles from the ELL queries and the
    vanilla one intersects per column. The planner builds a partition's
    table on the same predicate."""
    import re

    import jax

    from repro.core.tree import _tree_infer, reads_dense_table
    from repro.index import ScatterGatherPlanner, partition_tree

    tree, ws, x, xi, xv = small_tree
    n, d = xi.shape[0], tree.d
    table = re.compile(rf"tensor<{n}x{d + 1}xf32>")
    text = jax.jit(
        lambda a, b: _tree_infer(
            tuple(tree.layers), tree.n_cols, tree.branching, d, a, b,
            beam=4, topk=3, method=method, score_mode="prod", qt=4,
        )
    ).lower(xi, xv).as_text()
    dense = reads_dense_table(method)
    assert dense == (method == "mscm_dense")
    assert bool(table.search(text)) == dense
    assert ("scatter" in text) == dense
    planner = ScatterGatherPlanner(partition_tree(tree, 2), method=method)
    assert planner._needs_dense == dense


def test_tiles_padded_to_lanes(small_tree):
    """Every level stores its tiles ``[C, B, Rp]`` with Rp a multiple of the
    128-lane width; the padding rows hold the sentinel ``d`` and zeros."""
    tree, ws, *_ = small_tree
    for lay, b in zip(tree.layers, tree.branching):
        rows, vals = np.asarray(lay.chunk_rows), np.asarray(lay.chunk_vals)
        c, rp = rows.shape
        assert vals.shape == (c, b, rp) and rp % 128 == 0
        pad = rows == tree.d
        assert pad.any(), "the union widths here lie below 128"
        # the sentinel rows are a suffix of each chunk's row list
        assert (np.diff(pad.astype(np.int8), axis=1) >= 0).all()
        assert (vals.transpose(0, 2, 1)[pad] == 0).all()


def test_extract_scores_bitwise_as_full_tree(small_tree):
    """A sub-tree of sliced ``[C, B, Rp]`` tiles, phantom chunk included,
    scores its chunks bit for bit as the full tree does."""
    tree, ws, x, xi, xv = small_tree
    c0, c1 = 2, 6
    sub = tree.extract(1, c0, c1)
    full_lay, sub_lay = tree.layers[1], sub.layers[0]
    assert sub_lay.chunk_vals.shape == (c1 - c0 + 1,) + full_lay.chunk_vals.shape[1:]
    assert not np.asarray(sub_lay.chunk_vals[-1]).any()
    n = xi.shape[0]
    ids = jnp.asarray(np.random.default_rng(3).integers(c0, c1, (n, 3)),
                      jnp.int32)
    scores = jnp.ones((n, 3), jnp.float32)
    for method in ("mscm_pallas_grouped", "mscm_dense"):
        x_dense = None
        if method == "mscm_dense":
            x_dense = scatter_dense(xi, xv, tree.d)
        want = level_combined(full_lay, 8, tree.d, xi, xv, x_dense, ids,
                              scores, method=method, score_mode="prod")
        got = level_combined(sub_lay, 8, tree.d, xi, xv, x_dense, ids - c0,
                             scores, method=method, score_mode="prod")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nonuniform_branching(rng):
    d = 90
    ws = make_tree_weights(rng, d, [4, 32], 8)  # level branchings 4 then 8
    tree = XMRTree.from_weight_matrices(ws, [4, 8])
    x = random_sparse_csr(5, d, 10, rng)
    xi, xv = x.to_ell()
    ref = brute_force_scores(x.to_dense(), ws)
    _, l = tree.infer(jnp.asarray(xi), jnp.asarray(xv), beam=32, topk=1)
    np.testing.assert_array_equal(np.asarray(l)[:, 0], np.argmax(ref, axis=1))


def test_ragged_label_count(rng):
    """L not divisible by B: phantom columns must never be returned."""
    from repro.sparse import random_sparse_csc

    d, B = 80, 8
    ws = [random_sparse_csc(d, 6, 8, rng), random_sparse_csc(d, 42, 8, rng)]
    tree = XMRTree.from_weight_matrices(ws, [6, 8])
    x = random_sparse_csr(20, d, 12, rng)
    xi, xv = x.to_ell()
    _, l = tree.infer(jnp.asarray(xi), jnp.asarray(xv), beam=42, topk=10)
    assert np.asarray(l).max() < 42
