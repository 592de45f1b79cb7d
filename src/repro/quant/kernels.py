"""Quantized grouped MSCM: dequantize-in-register inside the tile matmul.

``method="mscm_pallas_grouped_q"`` is the grouped kernel
(:func:`repro.kernels.mscm_kernel.mscm_grouped`) with one extra input — the
per-(chunk, column) scale row — and one extra in-kernel op: the chunk tile
is widened ``int8 → f32`` and multiplied by its scale row **in VMEM**, right
before the [QT, R] × [B, R]ᵀ contraction. Everything else is shared with the
exact path: the chunk-major device grouping (``ops.group_blocks_device``),
the fused σ⊗parent epilogue, the gather-based unsort, and the canonical
``beam_select`` downstream — so the quantized tier changes *weight bits*,
never selection semantics.

HBM traffic per tile drops ~4× on the dominant operand (the [B, R] chunk
tile ships as int8; the [B] scale row is noise), which is the whole point:
the tier trades a bounded score perturbation (|err| ≤ scale/2 per weight,
measured contract in ``benchmarks/bench_quant.py``) for ~4× memory and
bandwidth.

Parity contract (pinned by tests + the ``quant_kernel_parity`` flag): the
in-register dequant computes exactly ``q.astype(f32) * scale`` — the same
elementwise reconstruction :func:`repro.quant.storage.dequantize_layer`
materializes — so running this kernel on a :class:`QuantizedTree` is
bitwise-identical (in interpret mode) to running the exact grouped kernel
on the dequantized f32 tree. Interpret mode follows the backend exactly as
for the exact kernels (``ops._auto_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mscm import F32
from repro.kernels.mscm_kernel import CONTRACT_R
from repro.kernels.ops import (
    DEFAULT_QT,
    _auto_interpret,
    group_blocks_device,
    intersect_query_tiles,
)


def _grouped_q_body(
    tc_ref, xg_ref, ps_ref, vals_ref, scales_ref, out_ref, *, mode, rows
):
    # The scale block holds ``rows`` consecutive chunks' rows; pick this
    # tile's chunk out of it.
    row = tc_ref[pl.program_id(0)] % rows
    scale = scales_ref[pl.ds(row, 1), :]                 # [1, B]
    # In-register dequant: widen the resident int8/fp8 chunk tile to f32 and
    # apply the per-column scale row while both live in VMEM — the f32 tile
    # never exists in HBM.
    v = vals_ref[0].astype(jnp.float32) * scale.T        # [B, R]
    acc = jax.lax.dot_general(
        xg_ref[0], v,
        dimension_numbers=CONTRACT_R,
        precision=F32,
        preferred_element_type=jnp.float32,
    )                                                    # [QT, B]
    if mode == "prod":
        acc = jax.nn.sigmoid(acc) * ps_ref[0]            # ps: [QT, 1]
    elif mode == "logsum":
        acc = jax.nn.log_sigmoid(acc) + ps_ref[0]
    out_ref[0] = acc


def mscm_grouped_q(
    xg_tiles: jax.Array,    # f32 [T, QT, R] gathered query rows per tile
    vals: jax.Array,        # int8/fp8 [C, B, R] quantized chunk tiles
    scales: jax.Array,      # f32 [C, B] per-(chunk, column) scales
    tile_chunk: jax.Array,  # int32 [T]
    parent_scores: Optional[jax.Array] = None,  # f32 [T, QT] beam scores
    *,
    mode: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """Quantized chunk-major tile matmul with the fused beam epilogue.

    Identical contract to :func:`~repro.kernels.mscm_kernel.mscm_grouped`
    (``mode`` ∈ none/prod/logsum, [T, QT, B] f32 out); the chunk tile and
    its scale row are both indexed by ``tile_chunk``, so a chunk-sorted grid
    keeps them VMEM-resident across every query tile that hits the chunk.
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
    # Mosaic wants every block's last two dims to be (8k, 128k) or the
    # array's own: parent scores ride as [T, QT, 1], and the [C, B] scales
    # are read 8 chunk rows at a time (the kernel picks its row) — a (1, B)
    # block breaks the rule, and a [C, 1, B] copy would pad each row to 8.
    rows = min(8, c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, qt, r), lambda i, tc: (i, 0, 0)),
            pl.BlockSpec((1, qt, 1), lambda i, tc: (i, 0, 0)),
            pl.BlockSpec((1, b, r), lambda i, tc: (tc[i], 0, 0)),
            pl.BlockSpec((rows, b), lambda i, tc: (tc[i] // rows, 0)),
        ],
        out_specs=pl.BlockSpec((1, qt, b), lambda i, tc: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_q_body, mode=mode, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, qt, b), jnp.float32),
        interpret=interpret,
        name="mscm_grouped_q",
    )(tile_chunk, xg_tiles, parent_scores[..., None], vals, scales)


def mscm_grouped_q_level(
    x_idx: jax.Array,          # int32 [n, Q]
    x_val: jax.Array,          # f32 [n, Q]
    d: int,
    rows: jax.Array,           # int32 [C, R]
    vals: jax.Array,           # int8/fp8 [C, B, R]
    scales: jax.Array,         # f32 [C, B]
    block_q: jax.Array,        # int32 [A]
    block_c: jax.Array,        # int32 [A]
    parent_scores: Optional[jax.Array] = None,  # f32 [A] (beam scores)
    *,
    qt: int = DEFAULT_QT,
    mode: str = "none",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One tree level through the quantized grouped kernel, fully in-jit.

    Mirrors :func:`repro.kernels.ops.mscm_grouped_level` exactly — same
    device grouping, same query tiles (``intersect_query_tiles``), same
    unsort — with the quantized kernel in the middle. Traceable inside an
    enclosing jit.
    """
    interp = _auto_interpret(interpret)
    c, b, _ = vals.shape
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
    tiles = mscm_grouped_q(
        xg, vals, scales, tile_chunk, ps, mode=mode, interpret=interp
    )                                                    # [T, QT, B]
    flat = tiles.reshape(-1, b)
    return flat[flat_pos[jnp.argsort(order)]]            # [A, B]


@functools.partial(
    jax.jit, static_argnames=("d", "qt", "mode", "interpret")
)
def mscm_pallas_grouped_q(
    x_idx: jax.Array,
    x_val: jax.Array,
    d: int,
    rows: jax.Array,
    vals: jax.Array,
    scales: jax.Array,
    block_q: jax.Array,
    block_c: jax.Array,
    parent_scores: Optional[jax.Array] = None,
    *,
    qt: int = DEFAULT_QT,
    mode: str = "none",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Jitted entry point mirroring ``ops.mscm_pallas_grouped`` (tests)."""
    return mscm_grouped_q_level(
        x_idx, x_val, d, rows, vals, scales, block_q, block_c, parent_scores,
        qt=qt, mode=mode, interpret=interpret,
    )
