"""Jitted wrappers around the MSCM Pallas kernels.

``interpret=None`` follows the backend: on a TPU the kernels are compiled by
Mosaic; on any other backend (the CPU test runs) the kernel body executes in
the Pallas interpreter, for correctness only. Nothing else switches it.

The grouped path is fully device-resident: :func:`group_blocks_device`
derives the chunk-major tiling *inside* the jit (no host round-trip) and
:func:`intersect_query_tiles` fills the query tiles from the ELL queries,
so the entire multi-level beam search — group, intersect, matmul tiles,
epilogue, top-k — compiles as one XLA program (paper §4, Alg. 3) with no
dense query table.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mscm import gather_query_rows
from repro.kernels.mscm_kernel import (
    mscm_fused,
    mscm_grouped,
    mscm_pregather,
)

# A dense f32 query row above this many elements does not fit comfortably in
# VMEM alongside the chunk tile; fall back to the pre-gathered kernel.
VMEM_ROW_LIMIT = 1 << 20

# Query-tile height of the grouped kernel: rows per [QT, R] x [R, B] matmul.
DEFAULT_QT = 8


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def sort_blocks_by_chunk(block_q: jax.Array, block_c: jax.Array):
    """In-jit chunk-major ordering (paper Alg. 3 line 6-8) + inverse perm."""
    order = jnp.argsort(block_c, stable=True)
    return block_q[order], block_c[order], order


def unsort(out_sorted: jax.Array, order: jax.Array) -> jax.Array:
    """Undo a permutation by *gathering* through its inverse.

    ``argsort(order)`` is the inverse permutation; a gather through it is
    TPU-friendly, unlike the scatter ``zeros.at[order].set(out)`` (scatters
    serialize on TPU and block fusion with the consumer).
    """
    return out_sorted[jnp.argsort(order)]


# ---------------------------------------------------------------------------
# Device-side grouping (paper Alg. 3, in-jit)
# ---------------------------------------------------------------------------

def grouped_tile_bound(a: int, qt: int, num_chunks: int) -> int:
    """Static worst-case tile count for A blocks grouped per chunk into
    QT-row tiles.

    The true count is  Σ_c ceil(m_c / qt)  over the chunks present, which is
    bounded by ``ceil(A/qt) + #distinct_chunks`` (each chunk wastes at most
    one ragged tile) and by ``A`` (each tile holds ≥ 1 block). Shapes must be
    static under jit, so we provision ``min`` of the two; padding tiles are
    masked out by the caller.
    """
    return max(1, min(a, -(-a // qt) + min(num_chunks, a)))


def group_blocks_device(
    block_c: jax.Array, qt: int, num_chunks: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """In-jit, scatter-free grouping of active blocks into per-chunk tiles.

    The static tile count is :func:`grouped_tile_bound`; every construction
    step is a sort, searchsorted, or gather (no scatters — see ``unsort``).

    Returns
      tile_chunk [T]      chunk id per tile (padding tiles repeat the last
                          real chunk so Pallas re-uses the resident tile
                          instead of DMA-ing a fresh one)
      tile_src   [T, QT]  index into the *unsorted* block list, -1 = padding
      order      [A]      chunk-major permutation of the block list
      flat_pos   [A]      position of sorted block i in the flattened
                          [T*QT] tile layout (strictly increasing)
    """
    a = block_c.shape[0]
    t = grouped_tile_bound(a, qt, num_chunks)
    order = jnp.argsort(block_c, stable=True)
    sc = block_c[order].astype(jnp.int32)                # [A] sorted chunks
    idx = jnp.arange(a, dtype=jnp.int32)
    run_start = jnp.searchsorted(sc, sc, side="left").astype(jnp.int32)
    rank = idx - run_start                               # position in run
    slot = rank % qt
    tile_id = jnp.cumsum((slot == 0).astype(jnp.int32)) - 1
    flat_pos = tile_id * qt + slot                       # strictly increasing
    # Invert sorted-position -> tile-slot by binary search (gather, not
    # scatter): flat slot f is occupied iff some flat_pos equals f.
    fgrid = jnp.arange(t * qt, dtype=flat_pos.dtype)
    j = jnp.minimum(jnp.searchsorted(flat_pos, fgrid), a - 1)
    hit = flat_pos[j] == fgrid
    tile_src = jnp.where(hit, order[j].astype(jnp.int32), -1).reshape(t, qt)
    # Chunk per tile from its slot-0 occupant; padding tiles (all at the
    # tail, chunks ascending) inherit the last real chunk via cummax.
    hit0 = hit.reshape(t, qt)[:, 0]
    j0 = j.reshape(t, qt)[:, 0]
    tile_chunk = jax.lax.cummax(jnp.where(hit0, sc[j0], 0))
    return tile_chunk, tile_src, order, flat_pos


def intersect_query_tiles(
    x_idx: jax.Array,          # int32 [n, Q] ELL ids, sentinel-padded (== d)
    x_val: jax.Array,          # f32 [n, Q]
    d: int,
    rows: jax.Array,           # int32 [C, R] chunk rows, sentinel-padded
    block_q: jax.Array,        # int32 [A]
    block_c: jax.Array,        # int32 [A]
    tile_src: jax.Array,       # int32 [T, QT] block per tile slot, -1 = pad
) -> jax.Array:
    """The grouped kernels' [T, QT, R] query tiles, by intersection.

    Each block's row holds its query's value at each of its chunk's rows:
    the query's ELL ids are compared with the chunk's row list and the
    matching values summed (the paper's intersection iterator, §4, as
    compare, select and add on the vector unit). A query's ids are
    distinct, so each element has at most one nonzero term and equals what
    a dense ``scatter_dense`` table would hold, bit for bit; a duplicated
    id sums, as the table's ``.add`` does. The intersection runs once per
    block ([A, Q] x [A, R] -> [A, R]); whole rows are then gathered into
    the tile layout through ``tile_src``, and padding slots are exact zeros.
    No [n, d+1] table is built.
    """
    xi = x_idx[block_q]                                  # [A, Q]
    xv = x_val[block_q]                                  # [A, Q]
    r = rows[block_c]                                    # [A, R]
    hit = xi[:, :, None] == r[:, None, :]                # [A, Q, R], fused
    xa = jnp.sum(jnp.where(hit, xv[:, :, None], 0), axis=1)   # [A, R]
    xa = jnp.where(r < d, xa, 0)                         # sentinel rows
    xg = xa[jnp.maximum(tile_src, 0)]                    # [T, QT, R]
    return jnp.where((tile_src >= 0)[..., None], xg, 0)


def mscm_grouped_level(
    x_idx: jax.Array,          # int32 [n, Q]
    x_val: jax.Array,          # f32 [n, Q]
    d: int,
    rows: jax.Array,           # int32 [C, R]
    vals: jax.Array,           # f32 [C, R, B]
    block_q: jax.Array,        # int32 [A]
    block_c: jax.Array,        # int32 [A]
    parent_scores: Optional[jax.Array] = None,  # f32 [A] (beam scores)
    *,
    qt: int = DEFAULT_QT,
    mode: str = "none",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One tree level through the MXU-tiled grouped kernel, fully in-jit.

    Groups the active blocks chunk-major on device, builds the [T, QT, R]
    query tiles by intersecting the ELL queries with the chunk rows
    (:func:`intersect_query_tiles`), runs one [QT, R] x [R, B] matmul per
    tile with the beam epilogue fused (``mode`` — see
    :func:`mscm_grouped`), and returns the [A, B] block scores in the
    original block order via a pure-gather unsort. Traceable: safe to call
    inside an enclosing jit.
    """
    interp = _auto_interpret(interpret)
    c, _, b = vals.shape
    tile_chunk, tile_src, order, flat_pos = group_blocks_device(
        block_c, qt, c
    )
    xg = intersect_query_tiles(
        x_idx, x_val, d, rows, block_q, block_c, tile_src
    )
    ps = None
    if parent_scores is not None:
        safe_src = jnp.maximum(tile_src, 0)              # [T, QT]
        ps = jnp.where(tile_src >= 0, parent_scores[safe_src], 0.0)
    tiles = mscm_grouped(
        xg, vals, tile_chunk, ps, mode=mode, interpret=interp
    )                                                    # [T, QT, B]
    # Gather-based unsort: sorted block i lives at tile flat slot
    # flat_pos[i]; composing with the inverse permutation restores the
    # original block order without a scatter.
    flat = tiles.reshape(-1, b)
    return flat[flat_pos[jnp.argsort(order)]]            # [A, B]


# ---------------------------------------------------------------------------
# Jitted entry points
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("variant", "sort", "interpret")
)
def mscm_pallas(
    x_dense: jax.Array,   # f32 [n, Dp]
    rows: jax.Array,      # int32 [C, R]
    vals: jax.Array,      # f32 [C, R, B]
    block_q: jax.Array,   # int32 [A]
    block_c: jax.Array,   # int32 [A]
    *,
    variant: str = "auto",
    sort: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Masked chunk multiplication via Pallas. Returns f32 [A, B]."""
    interp = _auto_interpret(interpret)
    if variant == "auto":
        variant = "fused" if x_dense.shape[1] <= VMEM_ROW_LIMIT else "pregather"
    if variant == "fused" and not interp:
        # Mosaic refuses the kernel's in-kernel 1-D gather over the VMEM
        # query row ("Only 2D gather is supported"); never swap in another
        # kernel behind the caller's back.
        raise NotImplementedError(
            "the fused MSCM kernel does not lower on TPU: its in-kernel "
            "1-D jnp.take gather is refused by Mosaic; use "
            "method='mscm_pallas_pregather' or 'mscm_pallas_grouped'"
        )
    if sort:
        bq, bc, order = sort_blocks_by_chunk(block_q, block_c)
    else:
        bq, bc, order = block_q, block_c, None
    if variant == "fused":
        out = mscm_fused(x_dense, rows, vals, bq, bc, interpret=interp)
    elif variant == "pregather":
        xg = gather_query_rows(x_dense, rows, bq, bc)
        out = mscm_pregather(xg, vals, bc, interpret=interp)
    else:
        raise ValueError(f"unknown variant {variant}")
    return unsort(out, order) if order is not None else out


@functools.partial(
    jax.jit, static_argnames=("d", "qt", "mode", "interpret")
)
def mscm_pallas_grouped(
    x_idx: jax.Array,
    x_val: jax.Array,
    d: int,
    rows: jax.Array,
    vals: jax.Array,
    block_q: jax.Array,
    block_c: jax.Array,
    parent_scores: Optional[jax.Array] = None,
    *,
    qt: int = DEFAULT_QT,
    mode: str = "none",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Batch-mode MXU-tiled MSCM, grouped *on device* — one XLA program.

    Blocks are packed per chunk into QT-row tiles in-jit
    (:func:`group_blocks_device`); one [QT, R] x [R, B] matmul per tile, with
    the beam epilogue fused when ``mode`` is ``prod``/``logsum``. Returns
    f32 [A, B] in the original block order.
    """
    return mscm_grouped_level(
        x_idx, x_val, d, rows, vals, block_q, block_c, parent_scores,
        qt=qt, mode=mode, interpret=interpret,
    )
