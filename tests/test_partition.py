"""Label-partitioned scatter–gather index: exactness, manifest, placement.

The tentpole contract (ISSUE 4): ``partition_tree(tree, P)`` +
``ScatterGatherPlanner`` must return results **bitwise-identical** to the
unpartitioned tree — same labels, same score bits — for every MSCM method,
across P × beam × qt × score_mode, including uneven label ranges (a ragged
last partition). The low-sync ``sync="final"`` mode is pinned to its weaker
contract: the merged top-k *dominates* the exact result.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import XMRTree
from repro.index import (
    PartitionManifest,
    ScatterGatherPlanner,
    assign_partitions,
    default_split_level,
    partition_tree,
    place,
    rebalance,
    rebalance_bounds,
    reference_topk_width,
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
    rng = np.random.default_rng(42)
    d, B = 150, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    x = random_sparse_csr(11, d, 16, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    return tree, xi, xv


def _assert_bitwise(planner, tree, xi, xv, beam, topk, method, score_mode, qt=8):
    ref_s, ref_l = tree.infer(
        xi, xv, beam=beam, topk=topk, method=method, score_mode=score_mode,
        qt=qt,
    )
    s, l = planner.infer(xi, xv)
    np.testing.assert_array_equal(np.asarray(l), np.asarray(ref_l))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))


# ---------------------------------------------------------------------------
# 1. exact-mode bitwise parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", [
    "vanilla", "mscm_dense", "mscm_pallas_grouped",
])
@pytest.mark.parametrize("n_partitions", [2, 4])
def test_partitioned_bitwise_every_method(tree_and_queries, method, n_partitions):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, n_partitions)
    pl = ScatterGatherPlanner(idx, beam=10, topk=5, method=method)
    _assert_bitwise(pl, tree, xi, xv, 10, 5, method, "prod")


@pytest.mark.parametrize("score_mode", ["prod", "logsum"])
@pytest.mark.parametrize("beam", [1, 6])
def test_partitioned_bitwise_beam_and_mode(tree_and_queries, beam, score_mode):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 3)
    pl = ScatterGatherPlanner(
        idx, beam=beam, topk=5, method="mscm_dense", score_mode=score_mode
    )
    _assert_bitwise(pl, tree, xi, xv, beam, 5, "mscm_dense", score_mode)


@pytest.mark.parametrize("qt", [4, 8])
def test_partitioned_bitwise_grouped_qt(tree_and_queries, qt):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 2)
    pl = ScatterGatherPlanner(
        idx, beam=6, topk=5, method="mscm_pallas_grouped", qt=qt
    )
    _assert_bitwise(pl, tree, xi, xv, 6, 5, "mscm_pallas_grouped", "prod", qt)


def test_uneven_label_ranges(rng):
    """L not divisible by B and P not dividing the chunk count: the last
    partition is smaller (the global ragged tail lands there) and phantom
    columns never surface."""
    d, B = 90, 8
    ws = [random_sparse_csc(d, 6, 8, rng), random_sparse_csc(d, 42, 8, rng)]
    tree = XMRTree.from_weight_matrices(ws, [6, 8])
    x = random_sparse_csr(15, d, 12, rng)
    xi, xv = map(jnp.asarray, x.to_ell())
    idx = partition_tree(tree, 4)  # 6 chunks over 4 partitions: [2,1,2,1]
    sizes = [p.n_labels for p in idx.manifest.partitions]
    assert sum(sizes) == 42
    assert sizes[-1] < max(sizes)  # ragged tail: last partition is smaller
    pl = ScatterGatherPlanner(idx, beam=5, topk=7, method="mscm_dense")
    _assert_bitwise(pl, tree, xi, xv, 5, 7, "mscm_dense", "prod")
    s, l = pl.infer(xi, xv)
    assert np.asarray(l).max() < 42


def test_deeper_split_level(tree_and_queries):
    """An explicit (non-default) split level also holds the contract."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4, level=2)
    assert idx.level == 2
    assert idx.head.depth == 2
    pl = ScatterGatherPlanner(idx, beam=6, topk=5, method="mscm_pallas_grouped")
    _assert_bitwise(pl, tree, xi, xv, 6, 5, "mscm_pallas_grouped", "prod")


# ---------------------------------------------------------------------------
# 2. hypothesis property: parity across P x beam x qt x score_mode
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
    def test_partition_parity_property(
        n_partitions, beam, qt, score_mode, method, seed
    ):
        """partition(tree, P).infer == tree.infer, bitwise, for arbitrary
        P x beam x qt x score_mode draws (ISSUE 4 satellite)."""
        rng = np.random.default_rng(seed)
        d, B = 100, 6
        ws = make_tree_weights(rng, d, [6, 36, 216], B, nnz_per_col=8)
        tree = XMRTree.from_weight_matrices(ws, B)
        x = random_sparse_csr(7, d, 12, rng)
        xi, xv = map(jnp.asarray, x.to_ell())
        idx = partition_tree(tree, n_partitions)
        pl = ScatterGatherPlanner(
            idx, beam=beam, topk=5, method=method, score_mode=score_mode,
            qt=qt,
        )
        ref_s, ref_l = tree.infer(
            xi, xv, beam=beam, topk=5, method=method, score_mode=score_mode,
            qt=qt,
        )
        s, l = pl.infer(xi, xv)
        np.testing.assert_array_equal(np.asarray(l), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_partition_parity_property():
        pass


# ---------------------------------------------------------------------------
# 3. final-merge (low-sync) mode: dominance, not bitwise
# ---------------------------------------------------------------------------

def test_final_mode_dominates_exact(tree_and_queries):
    """Partition-local beams retain candidates global pruning discarded:
    every merged score must be >= its exact counterpart (recall >=)."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4)
    pl = ScatterGatherPlanner(idx, beam=4, topk=5, sync="final")
    ref_s, _ = tree.infer(xi, xv, beam=4, topk=5, method="mscm_dense")
    s, l = pl.infer(xi, xv)
    assert s.shape == ref_s.shape
    assert np.all(np.asarray(s) >= np.asarray(ref_s))
    assert np.asarray(l).max() < tree.n_labels  # no phantom leaks


def test_reference_topk_width_matches_infer(tree_and_queries):
    tree, xi, xv = tree_and_queries
    for beam, topk in [(1, 10), (4, 5), (10, 10)]:
        s, _ = tree.infer(xi, xv, beam=beam, topk=topk)
        assert s.shape[1] == reference_topk_width(
            tree.n_cols, tree.branching, beam, topk
        )


# ---------------------------------------------------------------------------
# 4. manifest + extraction invariants
# ---------------------------------------------------------------------------

def test_manifest_ranges_and_memory(tree_and_queries):
    tree, *_ = tree_and_queries
    idx = partition_tree(tree, 4)
    m = idx.manifest
    # disjoint, contiguous, covering label ranges
    assert m.partitions[0].label_start == 0
    assert m.partitions[-1].label_end == tree.n_labels
    for a, b in zip(m.partitions, m.partitions[1:]):
        assert a.label_end == b.label_start
    # per-device model bytes shrink ~1/P (phantom pad chunks add slack)
    assert m.max_partition_bytes() < m.total_memory_bytes / 4 * 1.5
    assert m.shrink_ratio() > 2.0
    # hashes: content-derived, distinct per partition, stable across cuts
    hashes = [p.content_hash for p in m.partitions]
    assert len(set(hashes)) == len(hashes)
    m2 = partition_tree(tree, 4).manifest
    assert [p.content_hash for p in m2.partitions] == hashes


def test_manifest_json_roundtrip(tree_and_queries):
    tree, *_ = tree_and_queries
    m = partition_tree(tree, 3).manifest
    m2 = PartitionManifest.from_json(m.to_json())
    assert m2 == m


def test_partition_validation(tree_and_queries):
    tree, *_ = tree_and_queries
    with pytest.raises(ValueError):
        partition_tree(tree, 9, level=1)  # level 1 has only 8 chunks
    with pytest.raises(ValueError):
        partition_tree(tree, 513)  # deeper than any level's chunk count
    with pytest.raises(ValueError):
        partition_tree(tree, 0)
    with pytest.raises(ValueError):
        tree.head(0)
    with pytest.raises(ValueError):
        tree.extract(1, 5, 3)
    assert default_split_level(tree, 8) == 1
    assert default_split_level(tree, 9) == 2


def test_hit_counts(tree_and_queries):
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4)
    pl = ScatterGatherPlanner(idx, beam=10, topk=10)
    _, l = pl.infer(xi, xv)
    hits = pl.hit_counts(np.asarray(l))
    assert hits.sum() == np.asarray(l).size
    assert len(hits) == 4


# ---------------------------------------------------------------------------
# 5. placement (LPT packing)
# ---------------------------------------------------------------------------

def test_assign_partitions_balances_memory():
    mem = [100, 90, 40, 30, 20, 10]
    out = assign_partitions(mem, 2)
    loads = [sum(m for m, b in zip(mem, out) if b == col) for col in (0, 1)]
    assert abs(loads[0] - loads[1]) <= 30  # LPT: within the smallest item-ish
    assert sorted(set(out)) == [0, 1]
    with pytest.raises(ValueError):
        assign_partitions(mem, 0)


def test_place_single_device(tree_and_queries):
    """One local device: everything packs onto one model column and the
    planner still runs (and stays bitwise) through the placement path."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 2)
    pm = place(idx, shards=1)
    assert pm.n_model == 1 and pm.n_data == 1
    assert pm.assignments == [0, 0]
    assert sum(pm.column_loads(idx.manifest)) == sum(
        p.memory_bytes for p in idx.manifest.partitions
    )
    pl = ScatterGatherPlanner(idx, beam=6, topk=5, placement=pm)
    _assert_bitwise(pl, tree, xi, xv, 6, 5, "mscm_dense", "prod")


def test_place_occupancy_weighting(tree_and_queries):
    """Observed load shares (not memory) drive the packing when given."""
    tree, *_ = tree_and_queries
    idx = partition_tree(tree, 4)
    # Device-free pin of the LPT-by-load behavior (CI has one device, so
    # n_model == 1 and the placement itself degenerates): a partition
    # serving ~everything must sit alone on a column while the cold ones
    # share the other — memory packing (near-equal bytes) would pair it.
    load = [int(o * 1e6) for o in (0.94, 0.02, 0.02, 0.02)]
    cols = assign_partitions(load, 2)
    assert cols.count(cols[0]) == 1
    mem_cols = assign_partitions(
        [p.memory_bytes for p in idx.manifest.partitions], 2
    )
    assert mem_cols.count(mem_cols[0]) == 2  # bytes packing pairs them
    pm = place(idx, shards=1, occupancy=[0.94, 0.02, 0.02, 0.02])
    if pm.n_model == 2:  # full path needs >= 2 local devices
        assert pm.assignments.count(pm.assignments[0]) == 1
    with pytest.raises(ValueError):
        place(idx, occupancy=[0.5, 0.5])  # wrong arity
    with pytest.raises(ValueError):
        place(idx, occupancy=[-1.0, 1.0, 0.5, 0.5])


# ---------------------------------------------------------------------------
# 6. rebalance from observed occupancy skew
# ---------------------------------------------------------------------------

def test_rebalance_bounds_uniform_is_stable(tree_and_queries):
    """Uniform observed load keeps the even cut."""
    tree, *_ = tree_and_queries
    m = partition_tree(tree, 4).manifest
    assert rebalance_bounds(m, [0.25, 0.25, 0.25, 0.25]) == [0, 2, 4, 6, 8]


def test_rebalance_shrinks_hot_partition(tree_and_queries):
    """A partition serving 2x its share gives chunks to its neighbours."""
    tree, *_ = tree_and_queries
    m = partition_tree(tree, 4).manifest  # even cut: 2 chunks each
    bounds = rebalance_bounds(m, [0.70, 0.10, 0.10, 0.10])
    assert bounds[0] == 0 and bounds[-1] == 8
    assert all(b < a for b, a in zip(bounds, bounds[1:]))
    # The hot partition's new range is narrower than its old 2 chunks.
    assert bounds[1] - bounds[0] < 2


def test_rebalance_roundtrip_stays_bitwise(tree_and_queries):
    """Re-cutting from skew changes ranges, not results."""
    tree, xi, xv = tree_and_queries
    idx = partition_tree(tree, 4)
    idx2 = rebalance(tree, idx.manifest, [0.55, 0.15, 0.15, 0.15])
    sizes = [p.chunk_end - p.chunk_start for p in idx2.manifest.partitions]
    assert sizes != [2, 2, 2, 2]  # the cut actually moved
    assert idx2.manifest.partitions[-1].label_end == tree.n_labels
    for sync in ("level", "pipelined"):
        pl = ScatterGatherPlanner(idx2, beam=10, topk=5, sync=sync)
        _assert_bitwise(pl, tree, xi, xv, 10, 5, "mscm_dense", "prod")


def test_rebalance_validation(tree_and_queries):
    tree, *_ = tree_and_queries
    m = partition_tree(tree, 4).manifest
    with pytest.raises(ValueError):
        rebalance_bounds(m, [0.5, 0.5])          # wrong arity
    with pytest.raises(ValueError):
        rebalance_bounds(m, [0.0, 0.0, 0.0, 0.0])  # zero total
    with pytest.raises(ValueError):
        partition_tree(tree, 4, bounds=[0, 1, 2, 8])        # wrong length
    with pytest.raises(ValueError):
        partition_tree(tree, 4, bounds=[0, 3, 3, 5, 8])     # not increasing
    with pytest.raises(ValueError):
        partition_tree(tree, 4, bounds=[1, 3, 5, 7, 8])     # not covering
