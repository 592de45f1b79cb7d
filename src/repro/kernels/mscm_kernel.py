"""Pallas TPU kernels for Masked Sparse Chunk Multiplication.

Three kernels, all driven by a scalar-prefetched active-block list that the
caller sorts by chunk id (paper §4, final optimization: evaluate blocks in
chunk order so each chunk enters fast memory once). On TPU the sort is not
merely a cache *hint*: Pallas's pipelining skips re-copying an input block
whose ``index_map`` output is unchanged between consecutive grid steps, so a
chunk-sorted grid makes the chunk tile *structurally* VMEM-resident across
all the queries that hit it.

Kernels
-------
``fused``      dense-lookup analogue for small/medium d: the query's dense
               row lives in VMEM, the gather at the chunk's ELL rows happens
               in-kernel, followed by a [1,R]·[B,R]ᵀ contraction.
``pregather``  huge-d path (e.g. enterprise d = 4M, a dense row would blow
               VMEM): XLA gathers x at chunk rows in HBM, the kernel streams
               the pre-gathered [A, R] rows against chunk tiles.
``grouped``    MXU-tiled batch path: blocks grouped per chunk into query
               tiles of QT rows → one [QT,R]·[B,R]ᵀ matmul per tile, with the
               beam-search epilogue (σ(logit) ⊗ parent score, paper eq. 5)
               optionally fused into the kernel body so logits never
               round-trip through HBM between matmul and beam step. Grouping
               is device-side (:func:`repro.kernels.ops.group_blocks_device`)
               so the whole traversal compiles as one XLA program; the
               host-side :func:`group_blocks_by_chunk` remains as the
               reference grouping used by tests/benchmark accounting.

Alignment notes (TPU target; interpret mode ignores these):
* Mosaic requires the last two dims of every block to be multiples of
  (8, 128) or equal to the array's own; per-row operands therefore carry a
  unit middle dim (``[A, 1, R]``, ``[T, QT, 1]``) instead of a ``(1, R)``
  block over ``[A, R]``.
* Chunk tiles are stored ``[C, B, R]`` with R padded to a multiple of 128
  by ``ChunkedLayer.from_csc``, and every kernel contracts a query row
  ``[., R]`` with a tile ``[B, R]`` over their last axes. The reason is
  the layout XLA gives the stored array: a ``[C, R, B<128]`` array (or a
  ``[C, B, R]`` one with R not a multiple of 128) is laid out with C on the
  lanes, and XLA copies it into the kernel's row-major (8, 128) tiling, B
  padded to 128 lanes, on every call: 4x the tile bytes written, and the
  largest op of every dispatch. ``[C, B, R % 128 == 0]`` is already the
  kernel's layout, so the tiles are read where they lie.
* B < 128 underutilizes the MXU's output lanes; the grouped kernel's tiles
  put QT query rows on sublanes to compensate.
* The fused kernel's in-kernel 1-D gather (``jnp.take``) does not lower on
  Mosaic ("Only 2D gather is supported"): it runs in interpret mode only,
  and ``ops.mscm_pallas`` refuses it on a TPU backend.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mscm import F32

#: ``[M, R] · [B, R]ᵀ -> [M, B]``: a query row block against a chunk tile,
#: contracted over the lane axis R of both.
CONTRACT_R = (((1,), (1,)), ((), ()))


# ---------------------------------------------------------------------------
# fused: in-kernel gather from a VMEM-resident dense query row
# ---------------------------------------------------------------------------

def _fused_body(bq_ref, bc_ref, x_ref, rows_ref, vals_ref, out_ref):
    del bq_ref, bc_ref  # consumed by the index maps
    r = rows_ref[0, :]                                   # [R] int32
    xg = jnp.take(x_ref[0, :], r, mode="clip")           # [R] VMEM gather
    acc = jax.lax.dot_general(
        xg[None, :], vals_ref[0],
        dimension_numbers=CONTRACT_R,
        precision=F32,
        preferred_element_type=jnp.float32,
    )                                                    # [1, B]
    out_ref[0, :] = acc[0]


def mscm_fused(
    x_dense: jax.Array,   # f32 [n, Dp]  (Dp >= d+1; sentinel slot is zero)
    rows: jax.Array,      # int32 [C, R]
    vals: jax.Array,      # f32 [C, B, R]
    block_q: jax.Array,   # int32 [A]  sorted by block_c for chunk reuse
    block_c: jax.Array,   # int32 [A]
    *,
    interpret: bool = False,
) -> jax.Array:
    a = block_q.shape[0]
    _, dp = x_dense.shape
    c, b, r = vals.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(a,),
        in_specs=[
            pl.BlockSpec((1, dp), lambda i, bq, bc: (bq[i], 0)),
            pl.BlockSpec((1, r), lambda i, bq, bc: (bc[i], 0)),
            pl.BlockSpec((1, b, r), lambda i, bq, bc: (bc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda i, bq, bc: (i, 0)),
    )
    return pl.pallas_call(
        _fused_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((a, b), jnp.float32),
        interpret=interpret,
    )(block_q, block_c, x_dense, rows, vals)


# ---------------------------------------------------------------------------
# pregather: XLA does the HBM gather, kernel streams [1,R] x [B,R]^T
# ---------------------------------------------------------------------------

def _pregather_body(bc_ref, xg_ref, vals_ref, out_ref):
    del bc_ref
    acc = jax.lax.dot_general(
        xg_ref[0], vals_ref[0],
        dimension_numbers=CONTRACT_R,
        precision=F32,
        preferred_element_type=jnp.float32,
    )                                                    # [1, B]
    out_ref[0] = acc


def mscm_pregather(
    xg: jax.Array,        # f32 [A, R]  pre-gathered query values
    vals: jax.Array,      # f32 [C, B, R]
    block_c: jax.Array,   # int32 [A] sorted
    *,
    interpret: bool = False,
) -> jax.Array:
    a, r = xg.shape
    c, b, _ = vals.shape
    # Rows ride as [A, 1, R] / [A, 1, B] so every block's last two dims equal
    # the array's (a (1, R) block over [A, R] breaks Mosaic's (8, 128) rule).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(a,),
        in_specs=[
            pl.BlockSpec((1, 1, r), lambda i, bc: (i, 0, 0)),
            pl.BlockSpec((1, b, r), lambda i, bc: (bc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, b), lambda i, bc: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _pregather_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((a, 1, b), jnp.float32),
        interpret=interpret,
    )(block_c, xg[:, None, :], vals)
    return out.reshape(a, b)


# ---------------------------------------------------------------------------
# grouped: host-grouped chunk-major query tiles -> MXU matmuls
# ---------------------------------------------------------------------------

def group_blocks_by_chunk(
    block_c: np.ndarray, qt: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side grouping: pack active blocks into per-chunk tiles of QT.

    Returns
      tile_chunk [T]      chunk id of each tile
      tile_src   [T, QT]  index into the (unsorted) block list, -1 = padding
    """
    order = np.argsort(block_c, kind="stable")
    sorted_c = block_c[order]
    tiles_c, tiles_s = [], []
    i = 0
    a = len(block_c)
    while i < a:
        c = sorted_c[i]
        j = i
        while j < a and sorted_c[j] == c:
            j += 1
        members = order[i:j]
        for t0 in range(0, len(members), qt):
            grp = members[t0 : t0 + qt]
            src = np.full(qt, -1, dtype=np.int32)
            src[: len(grp)] = grp
            tiles_c.append(c)
            tiles_s.append(src)
        i = j
    if not tiles_c:  # degenerate empty input
        tiles_c, tiles_s = [0], [np.full(qt, -1, np.int32)]
    return np.asarray(tiles_c, np.int32), np.stack(tiles_s)


def _grouped_body(tc_ref, xg_ref, ps_ref, vals_ref, out_ref, *, mode):
    del tc_ref
    acc = jax.lax.dot_general(
        xg_ref[0], vals_ref[0],
        dimension_numbers=CONTRACT_R,
        precision=F32,
        preferred_element_type=jnp.float32,
    )                                                    # [QT, B]
    if mode == "prod":
        acc = jax.nn.sigmoid(acc) * ps_ref[0]            # ps: [QT, 1]
    elif mode == "logsum":
        acc = jax.nn.log_sigmoid(acc) + ps_ref[0]
    out_ref[0] = acc


def mscm_grouped(
    xg_tiles: jax.Array,   # f32 [T, QT, R] gathered query rows per tile
    vals: jax.Array,       # f32 [C, B, R]
    tile_chunk: jax.Array,  # int32 [T]
    parent_scores: Optional[jax.Array] = None,  # f32 [T, QT] beam scores
    *,
    mode: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """Chunk-major query-tile matmul with an optionally fused beam epilogue.

    ``mode``:
      ``none``    raw logits (the classic masked-matmul contract);
      ``prod``    σ(logit) · parent_score  (paper eq. 5, probability space);
      ``logsum``  logσ(logit) + parent_score  (log space).

    The epilogue runs on the [QT, B] accumulator while it is still in VMEM —
    the combined beam scores are the only thing written back to HBM.
    """
    t, qt, r = xg_tiles.shape
    c, b, _ = vals.shape
    if mode not in ("none", "prod", "logsum"):
        raise ValueError(f"unknown epilogue mode {mode!r}")
    if parent_scores is None:
        if mode != "none":
            raise ValueError(
                f"mode={mode!r} combines with the parent beam scores; pass "
                "parent_scores (zeros would silently flatten every score)"
            )
        parent_scores = jnp.zeros((t, qt), jnp.float32)
    # Parent scores ride as [T, QT, 1]: a (1, QT) block over [T, QT] breaks
    # Mosaic's rule that a block's last two dims be (8k, 128k) or full.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, qt, r), lambda i, tc: (i, 0, 0)),
            pl.BlockSpec((1, qt, 1), lambda i, tc: (i, 0, 0)),
            pl.BlockSpec((1, b, r), lambda i, tc: (tc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qt, b), lambda i, tc: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_body, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, qt, b), jnp.float32),
        interpret=interpret,
        name="mscm_grouped",
    )(tile_chunk, xg_tiles, parent_scores[..., None], vals)
