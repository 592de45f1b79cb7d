"""End-to-end tree inference: Algorithm 1 with every masked-matmul method.

Pins the paper's exactness claim at the system level under the contract
stated above ``core.tree.METHODS``: vanilla, MSCM (both iterators) and the
Pallas kernels return identical labels, and scores that agree to a few f32
ulp (each method sums the chunk dot products in its own order).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS, XMRTree
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


def test_grouped_program_holds_no_dense_table(small_tree):
    """The grouped program builds its query tiles from the ELL queries: the
    lowered ``_tree_infer`` holds no f32 [n, d+1] table and no scatter. The
    dense-lookup program, which keeps its table, is the control."""
    import re

    import jax

    from repro.core.tree import _tree_infer

    tree, ws, x, xi, xv = small_tree
    n, d = xi.shape[0], tree.d
    table = re.compile(rf"tensor<{n}x{d + 1}xf32>")

    def lowered(method):
        return jax.jit(
            lambda a, b: _tree_infer(
                tuple(tree.layers), tree.n_cols, tree.branching, d, a, b,
                beam=4, topk=3, method=method, score_mode="prod", qt=4,
            )
        ).lower(xi, xv).as_text()

    dense = lowered("mscm_dense")
    assert table.search(dense) and "stablehlo.scatter" in dense
    grouped = lowered("mscm_pallas_grouped")
    assert not table.search(grouped)
    assert "scatter" not in grouped


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
