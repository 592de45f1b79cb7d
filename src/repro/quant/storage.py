"""Quantized ELL chunk storage: int8/fp8 weights + pruned re-pack.

Layout. A :class:`~repro.core.tree.TreeLayerArrays` stores one level's chunk
tiles as ``chunk_vals`` f32 [C, B, R]. The quantized layer replaces that with
``chunk_vals`` int8 (or fp8-e4m3) [C, B, R] plus ``chunk_scales`` f32 [C, B]
— one symmetric scale per (chunk, column), i.e. per tree node, so a dominant
column cannot flatten its siblings' resolution. ``chunk_rows`` (the ELL row
indices, the masked-multiplication *mask*) stays exact int32: quantization
perturbs scores, never the sparsity pattern.

Scales: ``scale[c, b] = max_r |vals[c, b, r]| / Q`` with ``Q = 127`` (int8)
or ``448`` (fp8-e4m3 finite max); all-zero columns get scale 1 so dequant is
exactly 0 (and never divides by zero). For int8, ``q = rint(v / scale)``
clipped to ±127 — the worst-case dequant error is ``scale / 2`` per weight,
the bound the hypothesis property pins.

Pruned re-pack (:func:`prune_chunks`): per chunk, keep the top
``ceil(keep_frac · nnz_c)`` ELL rows by magnitude ``max_b |vals[c, :, r]|``
(ties break to the lower row index) and re-pack into a narrower pad width
``R' = round_up(max kept, 128)`` — the same lane alignment
``ChunkedLayer.from_csc`` applies. Kept weights are **bitwise** the
original f32 values when dequantized at the same scale grid; dropped rows
simply vanish from the mask.

Only the chunked layout is quantized: the per-column vanilla arrays exist
for the exact baseline method, which a compressed tier never dispatches.
:func:`dequantize_layer` therefore returns sentinel-only stubs for
``col_rows``/``col_vals`` — the dequantized tree serves every chunked MSCM
method, not ``method="vanilla"``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunked import lane_width
from repro.core.tree import TreeLayerArrays, XMRTree

#: Storage dtypes by name -> (numpy target dtype factory, symmetric qmax).
QUANT_DTYPES = {
    "int8": (np.int8, 127.0),
    "fp8": (np.dtype(jnp.float8_e4m3fn).type, 448.0),
}


@dataclasses.dataclass
class QuantLayerArrays:
    """Quantized device tensors for one level (a pytree).

    Mirrors :class:`~repro.core.tree.TreeLayerArrays` for the chunked layout
    (same field names where shared, so shape-only consumers — phantom
    clamping, chunk counts — work on either)."""

    chunk_rows: jax.Array    # int32 [C, R]  exact ELL mask (sentinel = d)
    chunk_vals: jax.Array    # int8/fp8 [C, B, R] quantized weights
    chunk_scales: jax.Array  # f32 [C, B] per-(chunk, column) symmetric scale


jax.tree_util.register_dataclass(
    QuantLayerArrays,
    data_fields=["chunk_rows", "chunk_vals", "chunk_scales"],
    meta_fields=[],
)


@dataclasses.dataclass
class QuantizedTree(XMRTree):
    """An :class:`XMRTree` whose layers are :class:`QuantLayerArrays`.

    Inherits the traversal machinery (``infer`` dispatches the quantized
    grouped method through the same ``_tree_infer``/``level_combined`` path,
    ``device_put``/``memory_bytes`` walk the layer pytrees) — only the
    per-level matmul changes. ``tier`` names the compression recipe so the
    manifest and fleet payloads can record it.
    """

    tier: str = "int8"

    def head(self, level: int) -> "XMRTree":
        raise TypeError(
            "QuantizedTree cannot be re-split: quantize per partition "
            "(repro.quant.quantize_index) after partition_tree()"
        )

    def extract(self, level: int, chunk_start: int, chunk_end: int) -> "XMRTree":
        raise TypeError(
            "QuantizedTree cannot be re-split: quantize per partition "
            "(repro.quant.quantize_index) after partition_tree()"
        )


def _dtype_for(tier: str) -> str:
    if tier in ("int8", "int8_pruned"):
        return "int8"
    if tier == "fp8":
        return "fp8"
    raise ValueError(f"no storage dtype for tier {tier!r}")


def quantize_layer(layer: TreeLayerArrays, dtype: str = "int8",
                   *, rows: np.ndarray | None = None,
                   vals: np.ndarray | None = None) -> QuantLayerArrays:
    """Symmetric per-(chunk, column) quantization of one level's chunk tiles.

    ``rows``/``vals`` override the layer's chunk arrays (the pruned re-pack
    path quantizes its narrower tiles through the same scale math).
    """
    np_dtype, qmax = QUANT_DTYPES[_dtype_for(dtype)]
    rows = np.asarray(layer.chunk_rows if rows is None else rows)
    vals = np.asarray(layer.chunk_vals if vals is None else vals,
                      dtype=np.float32)
    amax = np.abs(vals).max(axis=2)                      # [C, B]
    scale = (amax / qmax).astype(np.float32)
    scale = np.where(scale > 0, scale, np.float32(1.0))
    scaled = vals / scale[:, :, None]
    if np_dtype is np.int8:
        q = np.clip(np.rint(scaled), -qmax, qmax).astype(np.int8)
    else:
        # fp8 rounds to nearest representable; the clip is implicit (the
        # scale maps amax onto the finite max 448).
        q = np.asarray(jnp.asarray(scaled).astype(jnp.float8_e4m3fn))
    return QuantLayerArrays(
        chunk_rows=jnp.asarray(rows),
        chunk_vals=jnp.asarray(q),
        chunk_scales=jnp.asarray(scale),
    )


def dequantize_layer(qlayer: QuantLayerArrays, *, d: int) -> TreeLayerArrays:
    """f32 reconstruction ``q · scale`` of a quantized layer.

    The per-column vanilla arrays are sentinel-only stubs (see the module
    docstring): the reconstruction serves every *chunked* MSCM method.
    """
    vals = (
        np.asarray(qlayer.chunk_vals).astype(np.float32)
        * np.asarray(qlayer.chunk_scales)[:, :, None]
    )
    return TreeLayerArrays(
        chunk_rows=qlayer.chunk_rows,
        chunk_vals=jnp.asarray(vals),
        col_rows=jnp.full((1, 1), d, jnp.int32),
        col_vals=jnp.zeros((1, 1), jnp.float32),
    )


def prune_chunks(
    rows: np.ndarray,          # int32 [C, R] (sentinel = d)
    vals: np.ndarray,          # f32 [C, B, R]
    keep_frac: float,
    *,
    sentinel: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Magnitude-pruned ELL re-pack: keep the heavy rows, shrink R.

    Per chunk, the top ``ceil(keep_frac · nnz_c)`` rows by
    ``max_b |vals[c, :, r]|`` survive (stable: ties keep the lower row
    index); survivors are re-packed in ascending row order into a fresh pad
    width ``R' = lane_width(max kept)`` (:func:`repro.core.chunked.lane_width`:
    a multiple of 128 lanes). Kept values are copied bitwise; everything
    else becomes sentinel/0 padding.
    """
    if not 0.0 < keep_frac <= 1.0:
        raise ValueError(f"keep_frac must be in (0, 1]; got {keep_frac}")
    rows = np.asarray(rows)
    vals = np.asarray(vals)
    c, r = rows.shape
    valid = rows != sentinel                              # [C, R]
    mag = np.abs(vals).max(axis=1)                        # [C, R]
    mag = np.where(valid, mag, -1.0)                      # padding never kept
    nnz = valid.sum(axis=1)                               # [C]
    keep = np.ceil(keep_frac * nnz).astype(np.int64)      # [C], 0 when empty
    # Stable argsort on -mag: equal magnitudes stay in ascending row order.
    order = np.argsort(-mag, axis=1, kind="stable")       # [C, R]
    keep_mask = np.zeros_like(valid)
    np.put_along_axis(
        keep_mask, order,
        np.arange(r)[None, :] < keep[:, None], axis=1,
    )
    r_new = lane_width(int(keep.max(initial=1)))
    out_rows = np.full((c, r_new), sentinel, dtype=rows.dtype)
    out_vals = np.zeros((c, vals.shape[1], r_new), dtype=vals.dtype)
    for ci in range(c):
        src = np.flatnonzero(keep_mask[ci])               # ascending row order
        out_rows[ci, : len(src)] = rows[ci, src]
        out_vals[ci, :, : len(src)] = vals[ci][:, src]
    return out_rows, out_vals


def quantize_tree(
    tree: XMRTree, *, tier: str = "int8", prune_keep: float = 0.5
) -> QuantizedTree:
    """Compress every layer of ``tree`` into a :class:`QuantizedTree`.

    ``tier``: ``"int8"`` / ``"fp8"`` quantize in place; ``"int8_pruned"``
    first re-packs each chunk to its top ``prune_keep`` fraction of rows by
    magnitude (:func:`prune_chunks`), then quantizes the narrower tiles.
    """
    dtype = _dtype_for(tier)
    qlayers: List[QuantLayerArrays] = []
    for lay in tree.layers:
        rows = vals = None
        if tier == "int8_pruned":
            rows, vals = prune_chunks(
                np.asarray(lay.chunk_rows), np.asarray(lay.chunk_vals),
                prune_keep, sentinel=tree.d,
            )
        qlayers.append(quantize_layer(lay, dtype, rows=rows, vals=vals))
    return QuantizedTree(
        layers=qlayers, n_cols=tree.n_cols, branching=tree.branching,
        d=tree.d, tier=tier,
    )


def dequantize_tree(qtree: QuantizedTree) -> XMRTree:
    """f32 reconstruction of ``qtree`` (chunked methods only — see
    :func:`dequantize_layer`)."""
    return XMRTree(
        layers=[dequantize_layer(l, d=qtree.d) for l in qtree.layers],
        n_cols=qtree.n_cols,
        branching=qtree.branching,
        d=qtree.d,
    )


def quantize_index(index, *, tier: str = "int8", prune_keep: float = 0.5):
    """Compress a :class:`~repro.index.partition.PartitionedIndex` in place
    of its parts — the serving-tier entry point.

    The router head stays exact f32 (it is a few percent of the weights and
    its beam feeds *every* partition — quantizing it would perturb the
    handoff all tiers share). Each partition sub-tree is quantized after
    extraction, and the manifest is rebuilt so ``memory_bytes`` /
    ``content_hash`` describe the *compressed* bytes actually resident, with
    ``tier``/``dtype`` recorded per partition (manifest schema v2 — see
    ``src/repro/index/README.md``).
    """
    from repro.index.partition import _content_hash  # cycle-free at runtime

    dtype = _dtype_for(tier)
    np_dtype, _ = QUANT_DTYPES[dtype]
    qparts = [
        quantize_tree(p, tier=tier, prune_keep=prune_keep)
        for p in index.parts
    ]
    infos = [
        dataclasses.replace(
            info,
            memory_bytes=qp.memory_bytes(),
            content_hash=_content_hash(qp),
            tier=tier,
            dtype=np.dtype(np_dtype).name,
        )
        for info, qp in zip(index.manifest.partitions, qparts)
    ]
    manifest = dataclasses.replace(index.manifest, partitions=infos)
    return dataclasses.replace(index, parts=qparts, manifest=manifest)
