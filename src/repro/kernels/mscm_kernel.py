"""Pallas TPU kernel for Masked Sparse Chunk Multiplication.

The grouped kernel is the chip's path (``method="mscm_pallas_grouped"``):
active blocks are grouped per chunk into query tiles of QT rows, and each
tile is one [QT,R]·[B,R]ᵀ matmul on the MXU, with the beam-search epilogue
(σ(logit) ⊗ parent score, paper eq. 5) optionally fused into the kernel body
so logits never round-trip through HBM between matmul and beam step. The
tile list is driven by a scalar-prefetched chunk id per tile, chunk-major
(paper §4, final optimization: evaluate blocks in chunk order so each chunk
enters fast memory once). On TPU the order is not merely a cache *hint*:
Pallas's pipelining skips re-copying an input block whose ``index_map``
output is unchanged between consecutive grid steps, so a chunk-sorted grid
makes the chunk tile *structurally* VMEM-resident across all the queries
that hit it. Grouping is device-side
(:func:`repro.kernels.ops.group_blocks_device`) so the whole traversal
compiles as one XLA program; the host-side :func:`group_blocks_by_chunk`
remains as the reference grouping used by tests/benchmark accounting.

Alignment notes (TPU target; interpret mode ignores these):
* Mosaic requires the last two dims of every block to be multiples of
  (8, 128) or equal to the array's own; per-row operands therefore carry a
  unit middle dim (``[T, QT, 1]``) instead of a ``(1, QT)`` block over
  ``[T, QT]``.
* Chunk tiles are stored ``[C, B, R]`` with R padded to a multiple of 128
  by ``ChunkedLayer.from_csc``, and the kernel contracts a query tile
  ``[QT, R]`` with a chunk tile ``[B, R]`` over their last axes. The reason
  is the layout XLA gives the stored array: a ``[C, R, B<128]`` array (or a
  ``[C, B, R]`` one with R not a multiple of 128) is laid out with C on the
  lanes, and XLA copies it into the kernel's row-major (8, 128) tiling, B
  padded to 128 lanes, on every call: 4x the tile bytes written, and the
  largest op of every dispatch. ``[C, B, R % 128 == 0]`` is already the
  kernel's layout, so the tiles are read where they lie.
* B < 128 underutilizes the MXU's output lanes; the tiles put QT query rows
  on sublanes to compensate.
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
# grouped: chunk-major query tiles -> MXU matmuls
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
