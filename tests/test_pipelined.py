"""Pipelined scatter–gather execution: exactness, cache, overlap accounting.

The tentpole contract (ISSUE 5): ``sync="pipelined"`` — speculative
next-level expansion double-buffered against the coordinator's canonical
select — must be **bitwise-identical** to ``sync="level"`` (and hence to
the unpartitioned tree) for every MSCM method, across P × beam × qt ×
score_mode, including ragged trees and explicit split levels. The hot-beam
cache must never change a bit (it only skips partitions that could only
contribute ``NEG_INF``), and a cache *hit* must return exactly what the
cold run returned.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import XMRTree
from repro.index import (
    HotBeamCache,
    ScatterGatherPlanner,
    partition_tree,
    place,
)
from repro.sparse import random_sparse_csc, random_sparse_csr
from tests.conftest import make_tree_weights

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def tree_and_queries():
    rng = np.random.default_rng(7)
    d, B = 150, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    x = random_sparse_csr(11, d, 16, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    return tree, xi, xv


def _assert_bitwise(got, ref):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))


# ---------------------------------------------------------------------------
# 1. pipelined == level == unpartitioned, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", [
    "vanilla", "mscm_dense", "mscm_pallas_grouped",
])
@pytest.mark.parametrize("n_partitions", [2, 4])
def test_pipelined_bitwise_every_method(tree_and_queries, method, n_partitions):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, n_partitions)
    ref = tree.infer(xi, xv, beam=10, topk=5, method=method)
    pipe = ScatterGatherPlanner(
        idx, beam=10, topk=5, method=method, sync="pipelined"
    )
    _assert_bitwise(pipe.infer(xi, xv), ref)
    level = ScatterGatherPlanner(idx, beam=10, topk=5, method=method)
    _assert_bitwise(pipe.infer(xi, xv), level.infer(xi, xv))


@pytest.mark.parametrize("score_mode", ["prod", "logsum"])
@pytest.mark.parametrize("beam", [1, 6, 12])
def test_pipelined_bitwise_beam_and_mode(tree_and_queries, beam, score_mode):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 3)
    pl = ScatterGatherPlanner(
        idx, beam=beam, topk=5, method="mscm_dense", score_mode=score_mode,
        sync="pipelined",
    )
    ref = tree.infer(
        xi, xv, beam=beam, topk=5, method="mscm_dense", score_mode=score_mode
    )
    _assert_bitwise(pl.infer(xi, xv), ref)


@pytest.mark.parametrize("qt", [4, 8])
def test_pipelined_bitwise_grouped_qt(tree_and_queries, qt):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 2)
    pl = ScatterGatherPlanner(
        idx, beam=6, topk=5, method="mscm_pallas_grouped", qt=qt,
        sync="pipelined",
    )
    ref = tree.infer(
        xi, xv, beam=6, topk=5, method="mscm_pallas_grouped", qt=qt
    )
    _assert_bitwise(pl.infer(xi, xv), ref)


def test_pipelined_width_clamp(tree_and_queries):
    """beam=1, topk=10: the last level's candidate panel (b·B = 8) is
    narrower than topk — the merge must reproduce the reference clamp."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 3)
    pl = ScatterGatherPlanner(idx, beam=1, topk=10, sync="pipelined")
    ref = tree.infer(xi, xv, beam=1, topk=10)
    s, l = pl.infer(xi, xv)
    assert s.shape == ref[0].shape
    _assert_bitwise((s, l), ref)


def test_pipelined_uneven_label_ranges(rng):
    """Ragged tree (L not divisible by B, uneven chunk ranges): the junk
    id-shift and phantom parking still keep the speculation a superset."""
    d, B = 90, 8
    ws = [random_sparse_csc(d, 6, 8, rng), random_sparse_csc(d, 42, 8, rng)]
    tree = XMRTree.from_weight_matrices(ws, [6, 8])
    x = random_sparse_csr(15, d, 12, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    idx = partition_tree(tree, 4)
    pl = ScatterGatherPlanner(idx, beam=5, topk=7, sync="pipelined")
    ref = tree.infer(xi, xv, beam=5, topk=7)
    _assert_bitwise(pl.infer(xi, xv), ref)
    _, l = pl.infer(xi, xv)
    assert np.asarray(l).max() < 42


def test_pipelined_deeper_split_level(tree_and_queries):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4, level=2)
    pl = ScatterGatherPlanner(
        idx, beam=6, topk=5, method="mscm_pallas_grouped", sync="pipelined"
    )
    ref = tree.infer(xi, xv, beam=6, topk=5, method="mscm_pallas_grouped")
    _assert_bitwise(pl.infer(xi, xv), ref)


def test_pipelined_with_placement(tree_and_queries):
    """The placement path (explicit device hops) stays bitwise."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 2)
    pm = place(idx, shards=1)
    pl = ScatterGatherPlanner(idx, beam=6, topk=5, placement=pm,
                              sync="pipelined")
    ref = tree.infer(xi, xv, beam=6, topk=5)
    _assert_bitwise(pl.infer(xi, xv), ref)


def test_single_partition_pipelined(tree_and_queries):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 1)
    pl = ScatterGatherPlanner(idx, beam=8, topk=5, sync="pipelined")
    _assert_bitwise(pl.infer(xi, xv), tree.infer(xi, xv, beam=8, topk=5))


def test_invalid_sync_mode(tree_and_queries):
    tree, *_ = tree_and_queries
    idx = partition_tree(tree, 2)
    with pytest.raises(ValueError):
        ScatterGatherPlanner(idx, sync="speculative")
    with pytest.raises(ValueError):
        # final mode never consults the cache — a silent no-op is refused.
        ScatterGatherPlanner(idx, sync="final", cache_entries=8)


# ---------------------------------------------------------------------------
# 2. hypothesis property: pipelined == level for random trees/partitions
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        n_partitions=st.integers(2, 6),
        beam=st.integers(1, 12),
        qt=st.sampled_from([4, 8]),
        score_mode=st.sampled_from(["prod", "logsum"]),
        method=st.sampled_from(
            ["vanilla", "mscm_dense", "mscm_pallas_grouped"]
        ),
        seed=st.integers(0, 2**16),
    )
    def test_pipelined_equals_level_property(
        n_partitions, beam, qt, score_mode, method, seed
    ):
        """sync="pipelined" == sync="level", bitwise, for arbitrary
        P x beam x qt x score_mode draws (ISSUE 5 satellite)."""
        rng = np.random.default_rng(seed)
        d, B = 100, 6
        ws = make_tree_weights(rng, d, [6, 36, 216], B, nnz_per_col=8)
        tree = XMRTree.from_weight_matrices(ws, B)
        x = random_sparse_csr(7, d, 12, rng)
        xi, xv = map(jnp.asarray, x.to_ell())
        idx = partition_tree(tree, n_partitions)
        kw = dict(
            beam=beam, topk=5, method=method, score_mode=score_mode, qt=qt
        )
        level = ScatterGatherPlanner(idx, sync="level", **kw)
        pipe = ScatterGatherPlanner(idx, sync="pipelined", **kw)
        ref_s, ref_l = level.infer(xi, xv)
        s, l = pipe.infer(xi, xv)
        np.testing.assert_array_equal(np.asarray(l), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_pipelined_equals_level_property():
        pass


# ---------------------------------------------------------------------------
# 3. hot-beam cache: correctness and accounting
# ---------------------------------------------------------------------------

def test_cache_hit_bitwise_identical_to_cold(tree_and_queries):
    """A hot-beam cache hit must return exactly the cold run's bits —
    the ISSUE 5 cache-correctness pin."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4)
    ref = tree.infer(xi, xv, beam=10, topk=5)
    pl = ScatterGatherPlanner(
        idx, beam=10, topk=5, sync="pipelined", cache_entries=32
    )
    cold = pl.infer(xi, xv)
    assert pl.cache.misses > 0
    misses_after_cold = pl.cache.misses
    hot = pl.infer(xi, xv)
    # The second pass re-routes the same beams: all hits, no new misses.
    assert pl.cache.misses == misses_after_cold
    assert pl.cache.hits >= xi.shape[0]
    _assert_bitwise(cold, ref)
    _assert_bitwise(hot, ref)
    _assert_bitwise(hot, cold)


@pytest.mark.parametrize("sync", ["level", "pipelined"])
def test_cache_partition_skip_is_bitwise(sync):
    """Queries routed into one partition's label range: the cache skips the
    other partitions entirely and no bit changes."""
    rng = np.random.default_rng(3)
    d, B = 120, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    x = random_sparse_csr(9, d, 16, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    idx = partition_tree(tree, 4)
    ref = tree.infer(xi, xv, beam=2, topk=5)  # narrow beam -> few owners
    pl = ScatterGatherPlanner(
        idx, beam=2, topk=5, sync=sync, cache_entries=16
    )
    _assert_bitwise(pl.infer(xi, xv), ref)
    stats = pl.cache_stats()
    assert stats["misses"] > 0
    # The occupancy feed accumulated router-beam ownership.
    assert sum(stats["owner_counts"]) > 0


def test_cache_lru_eviction():
    cache = HotBeamCache(2, [0, 4, 8])
    a = np.array([[0, 1]])
    b = np.array([[4, 5]])
    c = np.array([[1, 6]])
    assert cache.active_partitions(a) == [0]
    assert cache.active_partitions(b) == [1]
    assert cache.active_partitions(c) == [0, 1]   # evicts a's entry
    assert cache.evictions == 1
    assert cache.active_partitions(b) == [1]      # still resident -> hit
    assert cache.hits == 1
    stats = cache.stats()
    assert stats["entries"] == 2 and stats["capacity"] == 2
    occ = cache.occupancy()
    assert occ.shape == (2,) and abs(occ.sum() - 1.0) < 1e-9


def test_cache_degenerate_beam_falls_back_to_all():
    cache = HotBeamCache(4, [0, 4, 8])
    # No valid id in range -> every partition stays active (safety).
    assert cache.active_partitions(np.array([[99, -1]])) == [0, 1]


def test_cache_validation():
    with pytest.raises(ValueError):
        HotBeamCache(0, [0, 4])
    with pytest.raises(ValueError):
        HotBeamCache(4, [0])


# ---------------------------------------------------------------------------
# 4. serving integration: pipelined + cache through the MicroBatcher
# ---------------------------------------------------------------------------

def test_pipelined_serving_engine_bitwise_and_metrics():
    from repro.serving import (
        BatchPolicy, MicroBatcher, ServeConfig, XMRServingEngine,
    )
    from repro.sparse import CSR

    rng = np.random.default_rng(11)
    d, B = 150, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    queries = random_sparse_csr(20, d, 16, rng)
    assert isinstance(queries, CSR)

    ref_s, ref_l = XMRServingEngine(
        tree, ServeConfig(max_batch=32)
    ).serve_batch(queries)

    engine = XMRServingEngine(
        tree,
        ServeConfig(
            max_batch=32, partitions=2, partition_sync="pipelined",
            beam_cache=16,
        ),
    )
    with MicroBatcher(engine, BatchPolicy(max_batch=8, max_wait_ms=1.0)) as mb:
        res = [f.result(timeout=60) for f in mb.submit_csr(queries)]
    s = np.stack([r[0] for r in res])
    l = np.stack([r[1] for r in res])
    np.testing.assert_array_equal(l, ref_l)
    np.testing.assert_array_equal(s, ref_s)

    summ = mb.metrics.summary()
    # Overlap accounting: every partitioned batch records its blocked wall.
    assert "pipeline_stall_avg_ms" in summ
    assert summ["pipeline_stall_avg_ms"] >= 0.0
    # Cache accounting: cumulative counters surface in the summary.
    assert summ["beam_cache"]["misses"] >= 1
    assert 0.0 <= summ["beam_cache"]["hit_rate"] <= 1.0
    # Unpartitioned engines don't record stall.
    assert engine.beam_cache_stats() is not None


def test_unpartitioned_engine_records_no_stall():
    from repro.serving import (
        BatchPolicy, MicroBatcher, ServeConfig, XMRServingEngine,
    )

    rng = np.random.default_rng(5)
    d, B = 100, 8
    ws = make_tree_weights(rng, d, [8, 64], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    queries = random_sparse_csr(6, d, 12, rng)
    engine = XMRServingEngine(tree, ServeConfig(max_batch=8))
    with MicroBatcher(engine, BatchPolicy(max_batch=4, max_wait_ms=1.0)) as mb:
        [f.result(timeout=60) for f in mb.submit_csr(queries)]
    summ = mb.metrics.summary()
    assert "pipeline_stall_avg_ms" not in summ
    assert "beam_cache" not in summ
