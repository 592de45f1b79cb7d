"""Operations and bytes that one level of masked sparse chunk
multiplication needs in any exact implementation.

A dispatch scores, at each level, the blocks ``(query, parent chunk)`` that
its beam visits. Whatever the layout, an exact implementation has to read
every weight nonzero of each distinct chunk visited in the dispatch at
least once (4 B each, f32) and every query nonzero (4 B each), and has to
multiply each weight nonzero of a chunk with its query's entry for every
block that visits the chunk (2 operations: a multiply and an add). The
counts use nonzeros, never the program's padded ``[R, B]`` tile or its
tile bound, so no kernel can read above its roofline.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

WEIGHT_BYTES = 4
QUERY_BYTES = 4


def chunk_nnz(col_nnz: np.ndarray, branching: int) -> np.ndarray:
    """Nonzeros of each chunk: the sum over its ``branching`` columns."""
    n = len(col_nnz)
    pad = (-n) % branching
    return np.concatenate([col_nnz, np.zeros(pad, col_nnz.dtype)]
                          ).reshape(-1, branching).sum(axis=1)


def level_work(visited: np.ndarray, nnz_of_chunk: np.ndarray,
               query_nnz: np.ndarray) -> Dict[str, float]:
    """Work of one level of one dispatch.

    ``visited`` is ``[n, b]``: the chunk ids each of the dispatch's ``n``
    queries scores at this level; ``query_nnz`` is ``[n]``.
    """
    distinct = np.unique(visited)
    return {
        "flops": 2.0 * float(nnz_of_chunk[visited].sum()),
        "bytes": float(WEIGHT_BYTES * nnz_of_chunk[distinct].sum()
                       + QUERY_BYTES * query_nnz.sum()),
        "chunks": float(len(distinct)),
    }


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes: float) -> float:
    """The roofline: the least time the chip needs for the work."""
    return max(flops / peak_flops, nbytes / peak_bytes)
