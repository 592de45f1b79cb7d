"""Plain reference for an XMR tree: beam search over CSC weights in numpy.

Paper Alg. 1 with ``prod`` scores (child = sigmoid(logit) * parent),
written against the per-level CSC weight arrays the benchmark generated.
It shares nothing with the program's chunk layout, kernels or tables:
f32 inputs, f64 sums and scores. Queries are handled in blocks through one
dense ``[block, d + 1]`` f32 table whose touched entries are zeroed again
after each block, so any ``d`` fits.

``precision="bf16_3x"`` is the control: the same search with every dot
product computed as a TPU computes an f32 matmul at ``Precision.HIGH``
(three bf16 passes: hi*hi + hi*lo + lo*hi, f32 accumulation) and the
scores kept in f32. It is the step below the ``HIGHEST`` precision the
configuration states, made bit-exact in numpy so it reads the same on any
machine.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

#: Candidates whose reference scores lie closer than this (relative) at a
#: beam or top-k cut may legitimately swap under f32 rounding.
TIE_RTOL = 2e-5


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round f32 to the nearest bfloat16 (ties to even), kept as f32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (r & np.uint32(0xFFFF0000)).view(np.float32)


def _dot_f64(xg: np.ndarray, w: np.ndarray) -> np.ndarray:
    return np.einsum("...r,...r->...", xg.astype(np.float64),
                     w.astype(np.float64))


def _dot_bf16_3x(xg: np.ndarray, w: np.ndarray) -> np.ndarray:
    xh = _bf16(xg)
    xl = _bf16(xg - xh)
    wh = _bf16(w)
    wl = _bf16(w - wh)
    prod = xh * wh + (xh * wl + xl * wh)
    return prod.sum(axis=-1, dtype=np.float32)


def near_tie_at_cut(ss: np.ndarray, k: int) -> np.ndarray:
    """Rows of ``ss`` (each sorted descending) where keeping the first
    ``k`` is not decided beyond rounding: some kept score and some dropped
    score differ, but by no more than ``TIE_RTOL``.

    Exactly equal scores are not near-ties: both sides order them by id.
    A group of equal scores can span the cut, so the pairs to look at are
    the smallest kept score above the first dropped one, and the largest
    dropped score below the last kept one.
    """
    if k >= ss.shape[1]:
        return np.zeros(ss.shape[0], bool)
    kept, dropped = ss[:, :k], ss[:, k:]
    first_out, last_in = ss[:, k:k + 1], ss[:, k - 1:k]
    up = np.where(kept > first_out, kept, np.inf).min(axis=1)
    down = np.where(dropped < last_in, dropped, -np.inf).max(axis=1)
    with np.errstate(invalid="ignore"):
        gap_up = (up - first_out[:, 0]) / up
        gap_down = (last_in[:, 0] - down) / last_in[:, 0]
    return (gap_up <= TIE_RTOL) | (gap_down <= TIE_RTOL)


@dataclasses.dataclass
class Level:
    """One level's columns as padded rows: ids (sentinel ``d``) and values."""

    idx: np.ndarray   # int32 [n_cols, width]
    val: np.ndarray   # f32   [n_cols, width]
    nnz: np.ndarray   # int64 [n_cols]

    @classmethod
    def from_csc(cls, indptr: np.ndarray, indices: np.ndarray,
                 data: np.ndarray, d: int) -> "Level":
        nnz = np.diff(indptr)
        width = max(1, int(nnz.max(initial=1)))
        offs = np.arange(width)
        valid = offs[None, :] < nnz[:, None]
        src = np.where(valid, indptr[:-1, None] + offs[None, :], 0)
        idx = np.where(valid, indices[src], d).astype(np.int32)
        val = np.where(valid, data[src], 0.0).astype(np.float32)
        return cls(idx=idx, val=val, nnz=nnz)


@dataclasses.dataclass
class Result:
    scores: np.ndarray        # [n, topk + 1] best first (score desc, id asc)
    labels: np.ndarray        # [n, topk + 1]
    ambiguous: np.ndarray     # bool [n]: a near-tie at some cut
                              # (:func:`near_tie_at_cut`)
    visited: List[np.ndarray]  # per level: [n, b] parent (chunk) ids scored


class TreeReference:
    """Beam search over ``levels``: CSC tuples ``(indptr, indices, data,
    (d, n_cols))`` from the top stored level to the leaves."""

    def __init__(self, levels: Sequence[Tuple], branching: int):
        self.d = int(levels[0][3][0])
        self.B = int(branching)
        self.n_cols = [int(lv[3][1]) for lv in levels]
        self.levels = [Level.from_csc(ip, ix, dt, self.d)
                       for ip, ix, dt, _ in levels]

    def search(self, q_indptr: np.ndarray, q_indices: np.ndarray,
               q_data: np.ndarray, rows: np.ndarray, *, beam: int, topk: int,
               precision: str = "f64", block: int = 32) -> Result:
        """Search the query rows ``rows`` of a CSR matrix."""
        dot = {"f64": _dot_f64, "bf16_3x": _dot_bf16_3x}[precision]
        sdt = np.float64 if precision == "f64" else np.float32
        rows = np.asarray(rows, np.int64)
        table = np.zeros((block, self.d + 1), np.float32)
        parts = []
        for b0 in range(0, len(rows), block):
            sel = rows[b0:b0 + block]
            lens = q_indptr[sel + 1] - q_indptr[sel]
            qpos = np.repeat(np.arange(len(sel)), lens)
            flat = np.concatenate([np.arange(q_indptr[r], q_indptr[r + 1])
                                   for r in sel])
            table[qpos, q_indices[flat]] = q_data[flat]
            parts.append(self._search_block(table, len(sel), beam, topk,
                                            dot, sdt))
            table[qpos, q_indices[flat]] = 0.0
        return Result(
            scores=np.concatenate([p.scores for p in parts]),
            labels=np.concatenate([p.labels for p in parts]),
            ambiguous=np.concatenate([p.ambiguous for p in parts]),
            visited=[np.concatenate([p.visited[i] for p in parts])
                     for i in range(len(self.levels))],
        )

    def _search_block(self, table, n, beam, topk, dot, sdt) -> Result:
        parents = np.zeros((n, 1), np.int64)
        scores = np.ones((n, 1), sdt)
        ambiguous = np.zeros(n, bool)
        visited = []
        q = np.arange(n)[:, None, None]
        for li, lev in enumerate(self.levels):
            last = li == len(self.levels) - 1
            visited.append(parents.copy())
            cols = (parents[:, :, None] * self.B
                    + np.arange(self.B)).reshape(n, -1)
            real = cols < self.n_cols[li]
            safe = np.where(real, cols, 0)
            xg = table[q, lev.idx[safe]]                   # [n, m, width]
            logits = dot(xg, lev.val[safe]).astype(sdt)
            par = np.repeat(scores, self.B, axis=1)
            s = par / (sdt(1.0) + np.exp(-logits))
            s = np.where(real, s, -np.inf)
            order = np.lexsort((cols, -s), axis=-1)        # score desc, id asc
            k = min(topk if last else beam, self.n_cols[li])
            ambiguous |= near_tie_at_cut(np.take_along_axis(s, order, axis=1),
                                         k)
            keep = order[:, : k + 1] if last else order[:, :k]
            parents = np.take_along_axis(cols, keep, axis=1)
            scores = np.take_along_axis(s, keep, axis=1)
        return Result(scores=scores.astype(np.float64), labels=parents,
                      ambiguous=ambiguous, visited=visited)
