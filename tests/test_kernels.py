"""Grouped Pallas MSCM kernel validation (interpret mode) against the oracles.

Sweeps shapes, query-tile heights and block orders; the grouped kernel must
match ``ref.mscm_ref`` allclose and the dense-lookup einsum to a few ulp.
TPU is the target; interpret=True executes the kernel body on CPU. The
hypothesis property sweep is skipped when hypothesis is not installed;
everything else runs everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dependency
    HAS_HYPOTHESIS = False

from repro.core import mscm as M
from repro.core.chunked import ChunkedLayer
from repro.kernels import ops
from repro.kernels import ref as ref_lib
from repro.kernels.mscm_kernel import group_blocks_by_chunk
from repro.sparse import random_sparse_csc, random_sparse_csr
from tests.conftest import assert_cross_method_close


def _mk(rng, n, d, C, B, nnz_w, nnz_x, A, *, ell=False):
    """Random chunked layer, queries and blocks; with ``ell`` the ELL
    queries (x_idx, x_val) lead the tuple."""
    w = random_sparse_csc(d, C * B, nnz_w, rng, sibling_groups=B)
    ch = ChunkedLayer.from_csc(w, B)
    x = random_sparse_csr(n, d, nnz_x, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    xd = M.scatter_dense(xi, xv, d)
    bq = rng.integers(0, n, size=A).astype(np.int32)
    bc = rng.integers(0, C, size=A).astype(np.int32)
    rows, vals = jnp.asarray(ch.rows), jnp.asarray(ch.vals)
    want = np.asarray(ref_lib.mscm_ref(xd, rows, vals, jnp.asarray(bq), jnp.asarray(bc)))
    out = (xd, rows, vals, bq, bc, want)
    return (xi, xv) + out if ell else out


@pytest.mark.parametrize("qt", [1, 2, 4, 8, 16])
def test_grouped_kernel(rng, qt):
    xi, xv, _, rows, vals, bq, bc, want = _mk(
        rng, n=7, d=72, C=5, B=8, nnz_w=7, nnz_x=11, A=17, ell=True
    )
    got = ops.mscm_pallas_grouped(
        xi, xv, 72, rows, vals, bq, bc, qt=qt, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("order", ["identity", "reversed", "shuffled"])
def test_grouped_block_order_invariance(rng, order):
    """Chunk-major grouping (paper's final §4 optimization) is a pure
    schedule change: any order of the block list scores each block as the
    oracle does, and returns the blocks in the order they were given."""
    xi, xv, xd, rows, vals, bq, bc, _ = _mk(
        rng, n=6, d=64, C=6, B=4, nnz_w=6, nnz_x=9, A=19, ell=True
    )
    perm = {
        "identity": np.arange(19),
        "reversed": np.arange(19)[::-1],
        "shuffled": rng.permutation(19),
    }[order]
    bq, bc = jnp.asarray(bq[perm]), jnp.asarray(bc[perm])
    want = np.asarray(ref_lib.mscm_ref(xd, rows, vals, bq, bc))
    got = ops.mscm_pallas_grouped(
        xi, xv, 64, rows, vals, bq, bc, qt=4, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("qt", [4, 8])
def test_grouped_one_chunk_revisit(rng, qt):
    """Every block on one chunk: the tiles fill to QT rows (a ragged last
    one at qt=8) and the grid revisits the same chunk tile at every step."""
    xi, xv, xd, rows, vals, _, _, _ = _mk(
        rng, n=12, d=80, C=3, B=8, nnz_w=8, nnz_x=10, A=1, ell=True
    )
    bq = jnp.arange(12, dtype=jnp.int32)
    bc = jnp.full((12,), 1, jnp.int32)
    tc, ts, _, _ = ops.group_blocks_device(bc, qt, 3)
    real = np.asarray(ts)[:, 0] >= 0
    assert real.sum() == -(-12 // qt) and (np.asarray(tc) == 1).all()
    assert (np.asarray(ts)[real] >= 0).sum() == 12
    want = np.asarray(ref_lib.mscm_ref(xd, rows, vals, bq, bc))
    got = ops.mscm_pallas_grouped(
        xi, xv, 80, rows, vals, bq, bc, qt=qt, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_grouped_bitwise_vs_dense_lookup(rng):
    """The grouped kernel's per-block result is the dense-lookup einsum's to
    a few ulp: the two sum the R terms in different orders."""
    xi, xv, xd, rows, vals, bq, bc, _ = _mk(
        rng, n=6, d=90, C=4, B=8, nnz_w=8, nnz_x=10, A=13, ell=True
    )
    dense = M.mscm_dense_lookup(xd, rows, vals, jnp.asarray(bq), jnp.asarray(bc))
    got = ops.mscm_pallas_grouped(
        xi, xv, 90, rows, vals, bq, bc, qt=4, interpret=True
    )
    assert_cross_method_close(got, dense)


@pytest.mark.parametrize("path", ["grouped", "mscm_dense", "int8_grouped"])
def test_lane_padded_tiles_match_old_layout_oracle(rng, path):
    """Every reader of the ``[C, B, Rp]`` tiles scores what the einsum
    oracle scores on the same weights laid out ``[C, R, B]``, to the
    cross-method contract: contracting over the last axis in place of the
    middle one changes the order in which the CPU sums the R terms, never
    the terms (the padding rows add exact zeros). The int8 kernel is, in
    addition, bitwise the exact kernel on its dequantized tiles."""
    from repro.core.tree import TreeLayerArrays
    from repro.quant import dequantize_layer, quantize_layer
    from repro.quant.kernels import mscm_pallas_grouped_q

    n, d, C, B, A = 7, 300, 5, 8, 23
    w = random_sparse_csc(d, C * B, 40, rng, sibling_groups=B)
    ch = ChunkedLayer.from_csc(w, B)
    assert ch.vals.shape == (C, B, ch.R) and ch.R % 128 == 0
    x = random_sparse_csr(n, d, 60, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    xd = M.scatter_dense(xi, xv, d)
    bq = jnp.asarray(rng.integers(0, n, size=A).astype(np.int32))
    bc = jnp.asarray(rng.integers(0, C, size=A).astype(np.int32))
    rows, vals = jnp.asarray(ch.rows), jnp.asarray(ch.vals)
    if path == "int8_grouped":
        q = quantize_layer(TreeLayerArrays(rows, vals, rows, rows))
        vals = dequantize_layer(q, d=d).chunk_vals
        got = mscm_pallas_grouped_q(
            xi, xv, d, q.chunk_rows, q.chunk_vals, q.chunk_scales, bq, bc,
            qt=4, interpret=True,
        )
        exact = ops.mscm_pallas_grouped(
            xi, xv, d, rows, vals, bq, bc, qt=4, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))
    elif path == "grouped":
        got = ops.mscm_pallas_grouped(
            xi, xv, d, rows, vals, bq, bc, qt=4, interpret=True
        )
    else:
        got = M.mscm_dense_lookup(xd, rows, vals, bq, bc)
    xg = xd[bq[:, None], rows[bc]]                       # [A, R]
    old = jnp.swapaxes(vals, 1, 2)                       # [C, R, B]
    want = jnp.einsum("ar,arb->ab", xg, old[bc], precision=M.F32)
    assert np.count_nonzero(np.asarray(want)) > 0
    assert_cross_method_close(got, want)


def test_grouped_kernel_bitwise_tile_by_tile(rng):
    """The grouped kernel's grid reads each tile's own chunk: bitwise the
    same ``[QT, Rp] · [B, Rp]ᵀ`` contraction done tile by tile outside
    Pallas."""
    from repro.kernels.mscm_kernel import CONTRACT_R

    xi, xv, _, rows, vals, bq, bc, _ = _mk(
        rng, n=7, d=300, C=5, B=8, nnz_w=40, nnz_x=60, A=23, ell=True
    )
    qt, b = 4, vals.shape[1]
    bq, bc = jnp.asarray(bq), jnp.asarray(bc)
    got = ops.mscm_pallas_grouped(
        xi, xv, 300, rows, vals, bq, bc, qt=qt, interpret=True
    )
    tc, ts, order, flat_pos = ops.group_blocks_device(bc, qt, vals.shape[0])
    tiles = ops.intersect_query_tiles(xi, xv, 300, rows, bq, bc, ts)
    dot = jax.jit(lambda a, w: jax.lax.dot_general(
        a, w, CONTRACT_R, precision=M.F32,
        preferred_element_type=jnp.float32))
    per_tile = np.stack([np.asarray(dot(tiles[t], vals[int(tc[t])]))
                         for t in range(tiles.shape[0])])
    want = per_tile.reshape(-1, b)[np.asarray(flat_pos)[np.argsort(order)]]
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", ["prod", "logsum"])
def test_grouped_fused_epilogue(rng, mode):
    """σ⊗parent epilogue fused in-kernel == epilogue applied to raw logits."""
    xi, xv, xd, rows, vals, bq, bc, _ = _mk(
        rng, n=6, d=90, C=4, B=8, nnz_w=8, nnz_x=10, A=13, ell=True
    )
    ps = jnp.asarray(rng.random(13).astype(np.float32))
    raw = M.mscm_dense_lookup(xd, rows, vals, jnp.asarray(bq), jnp.asarray(bc))
    if mode == "prod":
        want = jax.nn.sigmoid(raw) * ps[:, None]
    else:
        want = jax.nn.log_sigmoid(raw) + ps[:, None]
    got = ops.mscm_pallas_grouped(
        xi, xv, 90, rows, vals, bq, bc, ps, qt=4, mode=mode, interpret=True
    )
    assert_cross_method_close(got, want)
    # bitwise against the same kernel's raw logits, epilogue applied outside
    raw_k = ops.mscm_pallas_grouped(
        xi, xv, 90, rows, vals, bq, bc, qt=4, interpret=True
    )
    if mode == "prod":
        want_k = jax.nn.sigmoid(raw_k) * ps[:, None]
    else:
        want_k = jax.nn.log_sigmoid(raw_k) + ps[:, None]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_k))


def test_group_blocks_by_chunk():
    bc = np.array([3, 1, 3, 3, 0, 1], np.int32)
    tile_c, tile_src = group_blocks_by_chunk(bc, qt=2)
    # every block appears exactly once
    members = tile_src[tile_src >= 0]
    assert sorted(members.tolist()) == list(range(6))
    # each tile's members share the tile's chunk
    for t in range(len(tile_c)):
        for s in tile_src[t]:
            if s >= 0:
                assert bc[s] == tile_c[t]
    # chunk 3 has 3 members -> two tiles (2 + 1 padded)
    assert (tile_c == 3).sum() == 2


@pytest.mark.parametrize("qt", [1, 2, 4, 8])
def test_group_blocks_device_matches_host(rng, qt):
    """In-jit grouping reproduces the host reference packing exactly, with
    padding tiles masked and parked on the last resident chunk."""
    for _ in range(10):
        a = int(rng.integers(1, 40))
        c = int(rng.integers(1, 12))
        bc = rng.integers(0, c, size=a).astype(np.int32)
        want_c, want_s = group_blocks_by_chunk(bc, qt)
        tc, ts, order, flat_pos = jax.jit(
            ops.group_blocks_device, static_argnums=(1, 2)
        )(jnp.asarray(bc), qt, c)
        tc, ts, order, flat_pos = map(np.asarray, (tc, ts, order, flat_pos))
        t_static = ops.grouped_tile_bound(a, qt, c)
        assert len(tc) == t_static and len(want_c) <= t_static
        nreal = len(want_c)
        np.testing.assert_array_equal(tc[:nreal], want_c)
        np.testing.assert_array_equal(ts[:nreal], want_s)
        assert (ts[nreal:] == -1).all()
        # padding tiles revisit the last real chunk (no fresh DMA on TPU)
        assert (tc[nreal:] == want_c[-1]).all()
        # flat_pos round-trips each sorted block to its tile slot
        np.testing.assert_array_equal(ts.reshape(-1)[flat_pos], order)


def _dense_tile_oracle(xi, xv, d, rows, bq, bc, qt):
    """The grouped path's former tile builder: a scalar gather from the
    dense [n, d+1] table, padding slots masked to zero."""
    x_dense = M.scatter_dense(xi, xv, d)
    tile_chunk, tile_src, _, _ = ops.group_blocks_device(bc, qt, rows.shape[0])
    q = bq[jnp.maximum(tile_src, 0)]                     # [T, QT]
    r = rows[tile_chunk]                                 # [T, R]
    xg = x_dense[q[..., None], r[:, None, :]]            # [T, QT, R]
    return jnp.where((tile_src >= 0)[..., None], xg, 0.0), tile_src


@pytest.mark.parametrize("qt", [1, 8])
@pytest.mark.parametrize("width", ["below_nnz", "above_nnz"])
@pytest.mark.parametrize("dup", [False, True], ids=["distinct", "dup_id"])
def test_intersect_query_tiles_bitwise_vs_dense_gather(rng, qt, width, dup):
    """The intersection tile builder equals the dense-table gather bit for
    bit: sentinel-padded queries and chunk rows read zero, padding slots
    are exact zeros, a query with a duplicated id sums as the table's
    ``.add`` scatter does, and ELL truncation below the query's nnz reads
    only what the table would hold."""
    from repro.sparse.csr import rows_to_ell

    n, d, C, B, nnz_x = 9, 120, 6, 8, 14
    w = random_sparse_csc(d, C * B, 10, rng, sibling_groups=B)
    ch = ChunkedLayer.from_csc(w, B)
    rows = jnp.asarray(ch.rows)
    assert (np.asarray(rows) == d).any(), "chunk rows must hold sentinels"
    x = random_sparse_csr(n, d, nnz_x, rng)
    nnz = np.diff(x.indptr)
    q = int(nnz.min()) - 2 if width == "below_nnz" else int(nnz.max()) + 5
    xi, xv = rows_to_ell(x, np.arange(n), q)
    if width == "above_nnz":
        assert (xi == d).any(), "queries must hold sentinels"
    if dup:
        # Query 0 holds one id it shares with a chunk row in two slots.
        h = np.intersect1d(xi[0][xi[0] < d], np.asarray(rows))[0]
        p = int(np.flatnonzero(xi[0] == h)[0])
        other = 1 if p == 0 else 0
        xi[0, other] = h
    xi, xv = jnp.asarray(xi), jnp.asarray(xv)
    a = 29
    bq = jnp.asarray(rng.integers(0, n, size=a).astype(np.int32))
    bc = jnp.asarray(rng.integers(0, C, size=a).astype(np.int32))
    if dup:
        bq = bq.at[0].set(0)
        bc = bc.at[0].set(int(np.flatnonzero((np.asarray(rows) == h).any(1))[0]))
    want, tile_src = _dense_tile_oracle(xi, xv, d, rows, bq, bc, qt)
    got = ops.intersect_query_tiles(xi, xv, d, rows, bq, bc, tile_src)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    pad = np.asarray(tile_src) < 0
    if qt > 1:
        assert pad.any(), "the case must hold padding slots"
    assert (np.asarray(got)[pad] == 0).all()
    assert np.count_nonzero(np.asarray(got)) > 0
    if dup:
        v = np.asarray(xv)[0]
        assert np.float32(v[p] + v[other]) in np.asarray(got)


def test_force_interpret_env(monkeypatch):
    """Interpret mode follows the backend and nothing else: compiled on a
    TPU, interpreted elsewhere; an explicit argument always wins."""
    assert ops._auto_interpret(None) == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._auto_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._auto_interpret(None) is True
    assert ops._auto_interpret(True) is True
    assert ops._auto_interpret(False) is False


if HAS_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(1, 6),
        d=st.integers(8, 300),
        c=st.integers(1, 6),
        b=st.sampled_from([2, 8, 32]),
        nnz_w=st.integers(1, 12),
        nnz_x=st.integers(1, 16),
        a=st.integers(1, 16),
        qt=st.sampled_from([1, 2, 8]),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_grouped_property_sweep(n, d, c, b, nnz_w, nnz_x, a, qt, seed):
        rng = np.random.default_rng(seed)
        xi, xv, _, rows, vals, bq, bc, want = _mk(
            rng, n=n, d=d, C=c, B=b, nnz_w=min(nnz_w, d),
            nnz_x=min(nnz_x, d), A=a, ell=True,
        )
        got = ops.mscm_pallas_grouped(
            xi, xv, d, rows, vals, bq, bc, qt=qt, interpret=True
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_grouped_property_sweep():
        pass
