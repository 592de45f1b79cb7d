"""The work-count functions against a brute-force count on a tiny tree."""

import numpy as np
import pytest

from xmrbench_testkit import REPO  # noqa: F401  (sys.path)

from xmrbench import weights
from xmrbench.work import mscm_grouped as work


def test_level_work_matches_brute_force():
    rng = np.random.default_rng(3)
    d, n_cols, b = 500, 70, 8                         # ragged last chunk
    indptr, indices, _ = weights.level_weights(rng, d, n_cols, b, 12, 0.8)
    col_nnz = np.diff(indptr)
    per_chunk = work.chunk_nnz(col_nnz, b)
    assert per_chunk.shape == (9,) and per_chunk.sum() == col_nnz.sum()
    visited = rng.integers(0, 9, size=(6, 3))          # 6 queries, beam 3
    qnnz = rng.integers(1, 20, size=6)
    got = work.level_work(visited, per_chunk, qnnz)
    flops = nbytes = 0
    seen = set()
    for q in range(6):
        for c in visited[q]:
            cols = range(c * b, min((c + 1) * b, n_cols))
            flops += 2 * sum(int(col_nnz[j]) for j in cols)
            if c not in seen:
                seen.add(c)
                nbytes += 4 * sum(int(col_nnz[j]) for j in cols)
    nbytes += 4 * int(qnnz.sum())
    assert got == {"flops": flops, "bytes": nbytes, "chunks": len(seen)}


def test_least_seconds_takes_the_binding_roof():
    assert work.least_seconds(2e12, 1e9, 1e12, 1e9) == pytest.approx(2.0)
    assert work.least_seconds(1e9, 4e9, 1e12, 1e9) == pytest.approx(4.0)


def test_generated_levels_follow_the_configured_geometry():
    assert weights.level_sizes(670091, 32) == [21, 655, 20941, 670091]
    assert weights.level_sizes(501070, 32) == [16, 490, 15659, 501070]
    levels = weights.tree_weights(np.random.SeedSequence(9), 3000, 5000, 32,
                                  [64, 64, 64], 0.8)
    for indptr, indices, data, (d, n) in levels:
        nnz = np.diff(indptr)
        assert len(nnz) == n and nnz.max() == 64 and nnz.min() >= 56
        for j in range(0, n, 97):                      # sorted, distinct, < d
            col = indices[indptr[j]:indptr[j + 1]]
            assert (np.diff(col) > 0).all() and col.max() < d
        # A chunk's union stays within the shared pool plus private ids.
        lo, hi = indptr[0], indptr[min(32, n)]
        assert len(np.unique(indices[lo:hi])) <= 80 + 32 * 13
    again = weights.tree_weights(np.random.SeedSequence(9), 3000, 5000, 32,
                                 [64, 64, 64], 0.8)
    assert all((a[1] == b[1]).all() and (a[2] == b[2]).all()
               for a, b in zip(levels, again))
