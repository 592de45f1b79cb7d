"""Random ranker weights for one configuration, generated from a seed.

The repo's model of a pruned XMR tree (``assumed`` in each configuration
file): the B sibling columns of a chunk draw their supports from one shared
pool of ``col_nnz / sibling_overlap`` feature ids; each keeps
``round(col_nnz * sibling_overlap)`` of the pool plus private ids, for
exactly ``col_nnz`` nonzeros a column. Values are standard normal. Every step is a whole-array
numpy operation: no loop over columns or chunks.

The tree is balanced over the real label count with no padded label slots:
level sizes bottom-up are ``ceil(L / B**k)`` (:func:`level_sizes`), and
each level's last chunk is ragged.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np


def level_sizes(n_labels: int, branching: int) -> List[int]:
    """Column counts of the stored levels, top to bottom (root excluded)."""
    sizes = [int(n_labels)]
    while sizes[-1] > branching:
        sizes.append(-(-sizes[-1] // branching))
    return sizes[::-1]


def _distinct_rows(rng: np.random.Generator, n_rows: int, k: int,
                   d: int) -> np.ndarray:
    """``[n_rows, k]`` int64: ``k`` distinct ids drawn uniformly from
    ``[0, d)`` per row, in random order."""
    extra = max(8, k // 4)
    draw = np.sort(rng.integers(0, d, size=(n_rows, k + extra)), axis=1)
    dup = np.zeros(draw.shape, bool)
    dup[:, 1:] = draw[:, 1:] == draw[:, :-1]
    if (dup.sum(axis=1) > extra).any():
        raise ValueError(f"cannot draw {k} distinct ids from {d}")
    # A random k of each row's distinct ids; repeats sort behind them all.
    # The order argpartition leaves is up to numpy's build, so the picks
    # are sorted before the seeded shuffle: any machine draws the same.
    keys = np.where(dup, 2.0, rng.random(draw.shape))
    pick = np.sort(np.argpartition(keys, k - 1, axis=1)[:, :k], axis=1)
    return rng.permuted(np.take_along_axis(draw, pick, axis=1), axis=1)


def level_weights(rng: np.random.Generator, d: int, n_cols: int,
                  branching: int, col_nnz: int, sibling_overlap: float):
    """One level's CSC arrays ``(indptr, indices, data)``; columns sorted.

    Within a chunk the pool and every column's private ids are distinct,
    so each column holds exactly ``col_nnz`` nonzeros and every full
    chunk's union is ``pool + branching * private`` ids: the program's tile
    widths (its compiled shapes) are the same for every seed.
    """
    n_groups = -(-n_cols // branching)
    pool_size = min(d, max(1, int(col_nnz / max(sibling_overlap, 1e-3))))
    n_shared = int(round(col_nnz * sibling_overlap))
    n_priv = max(0, col_nnz - n_shared)
    ids = _distinct_rows(rng, n_groups, pool_size + branching * n_priv, d)
    pool = ids[:, :pool_size]
    priv = ids[:, pool_size:].reshape(n_groups * branching, n_priv)[:n_cols]
    keep = np.argpartition(rng.random((n_cols, pool_size)), n_shared - 1,
                           axis=1)[:, :n_shared]                # [n, S]
    group = np.arange(n_cols) // branching
    cols = np.sort(np.concatenate([pool[group[:, None], keep], priv], axis=1),
                   axis=1)
    indptr = np.arange(n_cols + 1, dtype=np.int64) * col_nnz
    indices = cols.reshape(-1).astype(np.int32)
    data = rng.standard_normal(indices.size, dtype=np.float32)
    return indptr, indices, data


def tree_weights(seed_seq: np.random.SeedSequence, d: int, n_labels: int,
                 branching: int, col_nnz: Sequence[int],
                 sibling_overlap: float):
    """Per-level ``(indptr, indices, data, (d, n_cols))`` tuples, top down.

    ``col_nnz`` gives the nonzeros a column of each stored level holds.
    Each level draws from its own child of ``seed_seq``.
    """
    sizes = level_sizes(n_labels, branching)
    if len(col_nnz) != len(sizes):
        raise ValueError(f"col_nnz has {len(col_nnz)} levels; the tree has "
                         f"{len(sizes)} ({sizes})")
    out = []
    for ss, n, nnz in zip(seed_seq.spawn(len(sizes)), sizes, col_nnz):
        rng = np.random.default_rng(ss)
        indptr, indices, data = level_weights(rng, d, n, branching, nnz,
                                              sibling_overlap)
        out.append((indptr, indices, data, (d, n)))
    return out
