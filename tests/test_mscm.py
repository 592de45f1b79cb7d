"""MSCM path correctness: every path == dense oracle == each other.

This pins the paper's headline claim (§4): MSCM is *exact* — it returns the
same masked product as the vanilla per-column baseline, whether through the
dense-lookup einsum or the grouped Pallas kernel (interpret mode here).
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed (pip install -e .[dev])")
from hypothesis import given, settings, strategies as st

from repro.core import mscm as M
from repro.core.chunked import ChunkedLayer, ColumnELLLayer
from repro.kernels import ops
from repro.kernels import ref as ref_lib
from repro.sparse import random_sparse_csc, random_sparse_csr


def _setup(rng, n=6, d=120, C=5, B=8, nnz_w=10, nnz_x=15, A=12):
    w = random_sparse_csc(d, C * B, nnz_w, rng, sibling_groups=B)
    ch = ChunkedLayer.from_csc(w, B)
    col = ColumnELLLayer.from_csc(w, B)
    x = random_sparse_csr(n, d, nnz_x, rng)
    xi, xv = x.to_ell()
    block_q = rng.integers(0, n, size=A).astype(np.int32)
    block_c = rng.integers(0, C, size=A).astype(np.int32)
    return w, ch, col, x, xi, xv, block_q, block_c


def _all_variants(ch, col, xi, xv, block_q, block_c, d, B):
    xd = M.scatter_dense(jnp.asarray(xi), jnp.asarray(xv), d)
    rows, vals = jnp.asarray(ch.rows), jnp.asarray(ch.vals)
    bq, bc = jnp.asarray(block_q), jnp.asarray(block_c)
    out = {
        "ref": ref_lib.mscm_ref(xd, rows, vals, bq, bc),
        "dense_lookup": M.mscm_dense_lookup(xd, rows, vals, bq, bc),
        "grouped": ops.mscm_pallas_grouped(
            jnp.asarray(xi), jnp.asarray(xv), d, rows, vals, bq, bc,
            interpret=True,
        ),
        "vanilla": M.vanilla_columns(
            jnp.asarray(xi), jnp.asarray(xv),
            jnp.asarray(col.rows), jnp.asarray(col.vals), bq, bc, B, d,
        ),
    }
    return {k: np.asarray(v) for k, v in out.items()}


def test_variants_match_oracle(rng):
    w, ch, col, x, xi, xv, bq, bc = _setup(rng)
    outs = _all_variants(ch, col, xi, xv, bq, bc, w.shape[0], ch.B)
    for name, val in outs.items():
        np.testing.assert_allclose(val, outs["ref"], rtol=1e-5, atol=1e-6, err_msg=name)


def test_matches_marching_pointer_oracle(rng):
    """Each block equals the paper's Algorithm 2 marching-pointer result."""
    w, ch, col, x, xi, xv, bq, bc = _setup(rng, A=8)
    d = w.shape[0]
    xd = M.scatter_dense(jnp.asarray(xi), jnp.asarray(xv), d)
    out = np.asarray(
        M.mscm_dense_lookup(xd, jnp.asarray(ch.rows), jnp.asarray(ch.vals),
                            jnp.asarray(bq), jnp.asarray(bc))
    )
    for a in range(len(bq)):
        q_idx, q_val = x.row(int(bq[a]))
        want = ref_lib.block_ref_marching(
            q_idx, q_val, ch.rows[int(bc[a])], ch.vals[int(bc[a])], d
        )
        np.testing.assert_allclose(out[a], want, rtol=1e-5, atol=1e-6)


def test_scatter_dense_sentinel_is_zero(rng):
    x = random_sparse_csr(4, 30, 5, rng)
    xi, xv = x.to_ell()
    xd = np.asarray(M.scatter_dense(jnp.asarray(xi), jnp.asarray(xv), 30))
    assert xd.shape == (4, 31)
    assert (xd[:, 30] == 0).all()
    np.testing.assert_allclose(xd[:, :30], x.to_dense(), rtol=1e-6)


def test_empty_query_rows(rng):
    """Queries with zero features score 0 on every block."""
    d, C, B = 40, 3, 4
    w = random_sparse_csc(d, C * B, 5, rng, sibling_groups=B)
    ch = ChunkedLayer.from_csc(w, B)
    xi = np.full((2, 4), d, np.int32)  # all padding
    xv = np.zeros((2, 4), np.float32)
    xd = M.scatter_dense(jnp.asarray(xi), jnp.asarray(xv), d)
    out = M.mscm_dense_lookup(
        xd, jnp.asarray(ch.rows), jnp.asarray(ch.vals),
        jnp.asarray([0, 1]), jnp.asarray([0, 2]),
    )
    assert not np.asarray(out).any()


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 8),
    d=st.integers(4, 150),
    c=st.integers(1, 5),
    b=st.sampled_from([2, 3, 8, 16]),
    nnz_w=st.integers(1, 10),
    nnz_x=st.integers(1, 20),
    seed=st.integers(0, 2**31 - 1),
)
def test_variants_match_property(n, d, c, b, nnz_w, nnz_x, seed):
    rng = np.random.default_rng(seed)
    w, ch, col, x, xi, xv, bq, bc = _setup(
        rng, n=n, d=d, C=c, B=b,
        nnz_w=min(nnz_w, d), nnz_x=min(nnz_x, d), A=min(2 * n, n * c),
    )
    outs = _all_variants(ch, col, xi, xv, bq, bc, d, b)
    for name, val in outs.items():
        np.testing.assert_allclose(val, outs["ref"], rtol=1e-4, atol=1e-5, err_msg=name)


def test_iterator_cost_table6():
    """Complexity counters mirror paper Table 6 orderings."""
    # queries much sparser than chunks -> hash/dense beat marching
    assert M.iterator_cost("hash", 10, 1000) < M.iterator_cost("marching", 10, 1000)
    # dense lookup amortizes with batch size
    c1 = M.iterator_cost("dense", 10, 1000, n_queries=1)
    c2 = M.iterator_cost("dense", 10, 1000, n_queries=1)  # same chunk cost
    assert c1 == c2
    big_batch = M.iterator_cost("dense", 1000, 10, n_queries=100)
    online = M.iterator_cost("dense", 1000, 10, n_queries=1)
    assert big_batch < online
    # binary search: min*log(max)
    assert M.iterator_cost("binsearch", 4, 1024) == 4 * 10
    with pytest.raises(ValueError):
        M.iterator_cost("bogus", 1, 1)


def test_chunk_vs_column_traversal_counts(rng):
    """Paper Item 1: chunking traverses once per chunk, not once per column."""
    d, B = 256, 32
    w = random_sparse_csc(d, B, 16, rng, sibling_groups=B, sibling_overlap=0.9)
    ch = ChunkedLayer.from_csc(w, B)
    mscm_len, vanilla_len = M.chunk_vs_column_traversals(ch.R, w.col_nnz(), B)
    assert mscm_len < vanilla_len  # shared support => union << sum
