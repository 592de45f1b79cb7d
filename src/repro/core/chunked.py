"""Column-chunked weight matrices — the MSCM data structure (paper §4, eq. 7-8).

A *chunk* is the group of ``B`` sibling columns of the level-``l`` weight
matrix ``W ∈ R^{d×L}`` that share a parent node at level ``l-1``. The paper
stores a chunk as a vertical sparse array of horizontal row vectors; the
TPU-native translation here is a per-chunk **ELL tile**:

    rows : int32 [C, R]      union of the sibling row supports, sorted and
                             padded with the sentinel ``d``
    vals : f32   [C, B, R]   dense (B × R) value tile per chunk, one row per
                             sibling column; positions where a sibling lacks
                             an entry, and the padding rows, hold explicit 0

The sibling-support-similarity observation (paper Item 2) is what makes the
``[B, R]`` tile dense enough to be profitable: R ≈ max-union-support per
chunk rather than B × per-column-support.

R is padded to a multiple of :data:`LANES`, the TPU's lane width, and sits
last, on the lanes. A TPU stores an array's two minor dims in (8, 128)
tiles; with B = 32 last, and R = 496 not a multiple of 128, XLA would
give the array a layout of its own and copy it, with B padded to 128
lanes, into the layout the kernels read on every call. ``[C, B, R]`` with R
a multiple of 128 is already that layout, so no call copies the tiles.
Padding costs 3% at R = 496 (to 512); a tree whose union width lies far
below 128 would store mostly zeros, and no configuration served here has
one.

Shapes are static once a model is loaded, which is what makes the whole beam
search jit-able with no dynamic sparsity in the control path.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.sparse.csr import CSC


#: The TPU lane width: the chunk tiles' row axis R is padded to a multiple.
LANES = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def lane_width(n: int) -> int:
    """Width of a tile row axis that holds ``n`` rows: ``n`` rounded up to
    a multiple of :data:`LANES`, and at least one lane row."""
    return _round_up(max(1, n), LANES)


@dataclasses.dataclass
class ChunkedLayer:
    """One tree level's weight matrix in chunked (MSCM) format."""

    rows: np.ndarray  # int32 [C, R], sentinel-padded (sentinel == d)
    vals: np.ndarray  # f32   [C, B, R]
    d: int            # feature dimension
    B: int            # branching factor == columns per chunk

    @property
    def C(self) -> int:
        return self.rows.shape[0]

    @property
    def R(self) -> int:
        return self.rows.shape[1]

    @property
    def n_cols(self) -> int:
        return self.C * self.B

    @property
    def nnz_dense_tile(self) -> int:
        """Elements actually stored (incl. explicit zeros) — memory model."""
        return int(self.vals.size)

    # ------------------------------------------------------------------
    @classmethod
    def from_csc(
        cls,
        w: CSC,
        branching: int,
    ) -> "ChunkedLayer":
        """Convert a CSC weight matrix to chunked format.

        Columns [i*B, (i+1)*B) form chunk i (labels are laid out in tree
        order, so siblings are contiguous — paper eq. 7). The last chunk is
        zero-padded if L % B != 0. R is the max over chunks of the union
        support size, rounded up to :data:`LANES` (see the module
        docstring); padding rows hold the sentinel ``d`` and zeros.
        The tiles are written in their ``[C, B, R]`` layout directly.
        """
        d, L = w.shape
        B = int(branching)
        C = (L + B - 1) // B
        # vectorized per chunk: union of sibling supports via np.unique, then
        # scatter values at searchsorted positions (no per-entry Python loop)
        unions = []
        width = 0
        col_start = w.indptr
        for c in range(C):
            lo = col_start[c * B]
            hi = col_start[min((c + 1) * B, L)]
            idx = np.unique(w.indices[lo:hi])
            unions.append(idx.astype(np.int32))
            width = max(width, len(idx))
        R = lane_width(width)
        rows = np.full((C, R), d, dtype=np.int32)
        vals = np.zeros((C, B, R), dtype=np.float32)
        for c, idx in enumerate(unions):
            rows[c, : len(idx)] = idx
            lo = col_start[c * B]
            hi = col_start[min((c + 1) * B, L)]
            ent_rows = w.indices[lo:hi]
            ent_vals = w.data[lo:hi]
            # column offset of every entry within the chunk
            n_cols = min((c + 1) * B, L) - c * B
            reps = np.diff(col_start[c * B : c * B + n_cols + 1]).astype(np.int64)
            ent_cols = np.repeat(np.arange(n_cols), reps)
            pos = np.searchsorted(idx, ent_rows)
            vals[c, ent_cols, pos] = ent_vals
        return cls(rows=rows, vals=vals, d=d, B=B)

    def to_dense(self) -> np.ndarray:
        """Reconstruct the dense [d, C*B] weight matrix (tests only)."""
        out = np.zeros((self.d + 1, self.n_cols), dtype=np.float32)
        for c in range(self.C):
            cols = slice(c * self.B, (c + 1) * self.B)
            np.add.at(out, (self.rows[c], cols), self.vals[c].T)
        return out[: self.d]

    def memory_bytes(self) -> int:
        return self.rows.nbytes + self.vals.nbytes

    def occupancy(self) -> float:
        """Fraction of the [C,B,R] tile holding true nonzeros (Item 2 metric)."""
        return float((self.vals != 0).mean())


@dataclasses.dataclass
class ColumnELLLayer:
    """Vanilla per-column layout (the paper's non-MSCM baseline, Alg. 4).

    Each column keeps its own sorted row list — the baseline traverses the
    query/column intersection once *per column* instead of once per chunk.
    """

    rows: np.ndarray  # int32 [L, Rc], sentinel-padded
    vals: np.ndarray  # f32   [L, Rc]
    d: int
    B: int            # branching factor (for block -> column expansion)

    @property
    def L(self) -> int:
        return self.rows.shape[0]

    @property
    def Rc(self) -> int:
        return self.rows.shape[1]

    @classmethod
    def from_csc(cls, w: CSC, branching: int, *, row_align: int = 8) -> "ColumnELLLayer":
        d, L = w.shape
        width = _round_up(max(1, int(w.col_nnz().max(initial=1))), row_align)
        rows, vals = w.to_col_ell(width)
        B = int(branching)
        Lp = _round_up(L, B)
        if Lp != L:  # pad phantom columns so chunk c covers cols [cB, cB+B)
            rows = np.concatenate([rows, np.full((Lp - L, width), d, np.int32)])
            vals = np.concatenate([vals, np.zeros((Lp - L, width), np.float32)])
        return cls(rows=rows, vals=vals, d=d, B=B)

    def memory_bytes(self) -> int:
        return self.rows.nbytes + self.vals.nbytes
