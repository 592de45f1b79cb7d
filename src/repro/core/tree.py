"""XMR tree model + beam-search inference (paper §3, Algorithm 1).

An :class:`XMRTree` holds one :class:`~repro.core.chunked.ChunkedLayer` per
tree level (plus the vanilla per-column layout for the baseline method) as
device arrays. ``infer`` runs the full beam search; the per-level masked
matmul dispatches to one of four methods (``METHODS``: the vanilla oracle,
dense-lookup MSCM, and the grouped Pallas kernel over f32 or quantized
tiles), and the exact ones return the same rankings — the paper's "free of
charge" property, pinned by tests under the contract stated above
``METHODS``. Every fact about a method lives in this module: the names
(:func:`check_method`), which one ``"auto"`` serves (:func:`resolve_method`),
which reads a dense query table (:func:`reads_dense_table`), and which
exact method stands in for the quantized one (:func:`exact_twin`).

Label layout convention: nodes at level l are numbered so that the children
of node p are [p*B, (p+1)*B) at level l+1 — chunk id == parent id, which is
what makes the beam's active-block list trivially static-shaped.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mscm as mscm_lib
from repro.core.beam import NEG_INF, beam_select, combine_scores
from repro.core.chunked import ChunkedLayer, ColumnELLLayer
from repro.sparse.csr import CSC

# Masked-matmul method selection. The exactness contract:
#   * within one method, results are bitwise identical across batch sizes,
#     topologies (sharded / partitioned / pipelined / fleet) and beam tiers;
#   * across the exact methods, labels are identical and scores agree to a
#     few f32 ulp (``assert_allclose`` at ``rtol`` ~1e-6): each method sums
#     the R-term chunk dot products in its own order (XLA einsum, Pallas
#     tile matmul, per-column dots), so the last bit may differ. Labels can
#     only differ where two candidates' scores lie within that tolerance.
#     Both need every f32 dot at ``core.mscm.F32`` (HIGHEST): a TPU's
#     default precision rounds the operands to bf16.
# The methods differ only in how the traversal maps to hardware. The one
# exception is the quantized tier's method (suffix ``_q``), which is exact
# *given its compressed weights* but approximate against the f32 tree:
#
#   vanilla               per-column sparse dots (paper Alg. 4 baseline).
#                         Correctness oracle; B× the traversal work.
#   mscm_dense            dense-lookup MSCM (paper §4 item 4): queries
#                         scattered into a dense [n, d+1] table, XLA gather +
#                         einsum. What ``"auto"`` serves off a TPU.
#   mscm_pallas_grouped   MXU-tiled grouped kernel: blocks packed per chunk
#                         into QT-row tiles *on device*, one [QT,R]·[B,R]ᵀ
#                         matmul per tile with the σ⊗parent beam epilogue
#                         fused in-kernel. What ``"auto"`` serves on a TPU,
#                         at any d — amortizes each chunk tile over up to QT
#                         queries and keeps the whole traversal in one XLA
#                         program. Query tiles come from intersecting the
#                         ELL queries with the chunk rows: no dense table.
#   mscm_pallas_grouped_q the grouped kernel over *quantized* chunk tiles
#                         (int8/fp8 + per-column scales, repro.quant):
#                         dequantize-in-register before the tile matmul.
#                         The one approximate member — bitwise-identical
#                         (interpret mode) to mscm_pallas_grouped on the
#                         *dequantized* weights,
#                         but the weights themselves carry quantization
#                         error (measured contract, benchmarks/bench_quant).
METHODS = (
    "vanilla",
    "mscm_dense",
    "mscm_pallas_grouped",
    "mscm_pallas_grouped_q",
)


def check_method(method: str) -> str:
    """``method`` itself if :data:`METHODS` holds it; a ``ValueError`` naming
    them otherwise. Entry points call it before anything is traced."""
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; choose from {METHODS}")
    return method


def reads_dense_table(method: str) -> bool:
    """Whether ``method`` scores from the dense ``[n, d+1]`` query table
    (:func:`repro.core.mscm.scatter_dense`) that its caller builds once per
    batch. Only the dense-lookup path does."""
    return method == "mscm_dense"


def exact_twin(method: str) -> str:
    """The method that scores f32 tiles as ``method`` scores its own: the
    exact grouped kernel for the quantized one (same grouping, same
    epilogue), ``method`` itself otherwise."""
    return "mscm_pallas_grouped" if method == "mscm_pallas_grouped_q" else method


def resolve_method(method: str, tier: str = "exact") -> str:
    """The method a server runs for a configured ``method`` and quantization
    ``tier``.

    ``"auto"`` resolves to the device-grouped MXU-tiled Pallas kernel on a
    TPU (the paper's batch-mode fast path, fully inside the ``_tree_infer``
    jit) and to the dense-lookup einsum elsewhere: Pallas interpret mode is
    for validation, not speed. A compressed tier stores int8/fp8 chunk
    tiles, which only the quantized grouped kernel reads; naming an exact
    method beside one is a contradiction, refused rather than overridden.
    """
    if tier != "exact":
        if method not in ("auto", "mscm_pallas_grouped_q"):
            raise ValueError(
                f"quant tier {tier!r} serves via "
                f"method='mscm_pallas_grouped_q'; got explicit "
                f"method={method!r}"
            )
        return "mscm_pallas_grouped_q"
    if method == "auto":
        return (
            "mscm_pallas_grouped"
            if jax.default_backend() == "tpu"
            else "mscm_dense"
        )
    return check_method(method)


@dataclasses.dataclass
class TreeLayerArrays:
    """Device-resident tensors for one level (a pytree)."""

    chunk_rows: jax.Array  # int32 [C, R]
    chunk_vals: jax.Array  # f32 [C, B, R], R a multiple of 128 (core.chunked)
    col_rows: jax.Array    # int32 [L, Rc] (vanilla baseline layout)
    col_vals: jax.Array    # f32 [L, Rc]


jax.tree_util.register_dataclass(
    TreeLayerArrays,
    data_fields=["chunk_rows", "chunk_vals", "col_rows", "col_vals"],
    meta_fields=[],
)


@dataclasses.dataclass
class XMRTree:
    layers: List[TreeLayerArrays]
    n_cols: Tuple[int, ...]     # true (unpadded) label count per level
    branching: Tuple[int, ...]  # B per level
    d: int

    @property
    def depth(self) -> int:
        return len(self.layers)

    @property
    def n_labels(self) -> int:
        return self.n_cols[-1]

    # ------------------------------------------------------------------
    @classmethod
    def from_weight_matrices(
        cls, weights: Sequence[CSC], branching: int | Sequence[int]
    ) -> "XMRTree":
        """Build from per-level CSC weight matrices W^(l), l = 2..depth.

        ``weights[i]`` scores the nodes of level i+2; level sizes must follow
        the chunk layout: L_{l+1} chunks == L_l columns (ragged trees are
        padded by the converters)."""
        bs = (
            [int(branching)] * len(weights)
            if np.isscalar(branching)
            else [int(b) for b in branching]
        )
        layers, ncols = [], []
        for w, b in zip(weights, bs):
            ch = ChunkedLayer.from_csc(w, b)
            col = ColumnELLLayer.from_csc(w, b)
            layers.append(
                TreeLayerArrays(
                    chunk_rows=jnp.asarray(ch.rows),
                    chunk_vals=jnp.asarray(ch.vals),
                    col_rows=jnp.asarray(col.rows),
                    col_vals=jnp.asarray(col.vals),
                )
            )
            ncols.append(w.shape[1])
        return cls(layers=layers, n_cols=tuple(ncols), branching=tuple(bs), d=weights[0].shape[0])

    def device_put(self, sharding) -> "XMRTree":
        """Copy of the tree with every layer tensor placed per ``sharding``.

        With a replicated ``NamedSharding(mesh, P())`` this is the serving
        tier's multi-device path: one physical copy per device, after which
        data-sharded query batches fan out over the mesh for free.
        """
        layers = [
            jax.tree.map(lambda a: jax.device_put(a, sharding), l)
            for l in self.layers
        ]
        return dataclasses.replace(self, layers=layers)

    def memory_bytes(self) -> int:
        tot = 0
        for l in self.layers:
            tensors = [l.chunk_rows, l.chunk_vals]
            scales = getattr(l, "chunk_scales", None)  # quantized layers
            if scales is not None:
                tensors.append(scales)
            tot += sum(np.asarray(t).nbytes for t in tensors)
        return tot

    # -- split / extract (label-space partitioning, repro.index) -----------
    def head(self, level: int) -> "XMRTree":
        """Top ``level`` stored layers as a standalone tree (the router).

        The head's leaves are the nodes of level ``level - 1`` — exactly the
        chunk ids of layer ``level`` — so ``head(level).infer(...,
        beam=b, topk=b)`` reproduces the unpartitioned traversal's beam state
        after ``level`` levels bit-for-bit (its internal "last level" uses
        ``next_b = min(b, n_cols[level-1])``, the same clamp the full
        traversal applies at a non-last level).
        """
        if not 1 <= level < self.depth:
            raise ValueError(f"head level must be in [1, {self.depth}); got {level}")
        return XMRTree(
            layers=list(self.layers[:level]),
            n_cols=self.n_cols[:level],
            branching=self.branching[:level],
            d=self.d,
        )

    def extract(self, level: int, chunk_start: int, chunk_end: int) -> "XMRTree":
        """Sub-tree owning chunks ``[chunk_start, chunk_end)`` of layer
        ``level`` down to the leaves, as a standalone :class:`XMRTree`.

        Layer tensors are *slices* of this tree's arrays — the ELL pad widths
        R/Rc are preserved, so every per-column dot product in the sub-tree is
        bitwise-identical to the same column scored through the full tree.
        Each level additionally gains one **phantom chunk** (all-sentinel
        rows, zero values, logits exactly 0): out-of-partition beam entries
        are parked there, their children ids land at/after the local label
        count, and the standard phantom-column mask re-pins their scores to
        ``NEG_INF`` at every level — they can never collide with a real
        label or surface in a merge.
        """
        if not 1 <= level < self.depth:
            raise ValueError(f"extract level must be in [1, {self.depth}); got {level}")
        if not 0 <= chunk_start < chunk_end:
            raise ValueError(f"bad chunk range [{chunk_start}, {chunk_end})")
        layers, ncols = [], []
        c0, c1 = chunk_start, chunk_end
        for li in range(level, self.depth):
            lay = self.layers[li]
            b = self.branching[li]
            c_global = lay.chunk_rows.shape[0]
            # The last partition's range can overrun the ragged global tail
            # at deeper levels (fewer real chunks than chunk_end * B): clamp.
            c1 = min(c1, c_global)
            if c0 >= c1:
                raise ValueError(
                    f"chunk range start {c0} has no real chunks at layer {li} "
                    f"({c_global} total)"
                )
            n_local = min(c1 * b, self.n_cols[li]) - c0 * b
            if n_local <= 0:
                raise ValueError(
                    f"chunk range [{c0}, {c1}) holds no real columns at "
                    f"layer {li}"
                )
            cr = lay.chunk_rows[c0:c1]
            cv = lay.chunk_vals[c0:c1]
            phantom_rows = jnp.full((1,) + cr.shape[1:], self.d, cr.dtype)
            phantom_vals = jnp.zeros((1,) + cv.shape[1:], cv.dtype)
            col_r = lay.col_rows[c0 * b : c1 * b]
            col_v = lay.col_vals[c0 * b : c1 * b]
            pcol_r = jnp.full((b,) + col_r.shape[1:], self.d, col_r.dtype)
            pcol_v = jnp.zeros((b,) + col_v.shape[1:], col_v.dtype)
            layers.append(
                TreeLayerArrays(
                    chunk_rows=jnp.concatenate([cr, phantom_rows]),
                    chunk_vals=jnp.concatenate([cv, phantom_vals]),
                    col_rows=jnp.concatenate([col_r, pcol_r]),
                    col_vals=jnp.concatenate([col_v, pcol_v]),
                )
            )
            ncols.append(n_local)
            c0, c1 = c0 * b, c1 * b
        return XMRTree(
            layers=layers,
            n_cols=tuple(ncols),
            branching=self.branching[level:],
            d=self.d,
        )

    # ------------------------------------------------------------------
    def infer(
        self,
        x_idx: jax.Array,  # int32 [n, Q] sorted, sentinel-padded
        x_val: jax.Array,  # f32 [n, Q]
        *,
        beam: int = 10,
        topk: int = 10,
        method: str = "mscm_dense",
        score_mode: str = "prod",
        qt: int = 8,
        init_parent_ids: jax.Array | None = None,
        init_scores: jax.Array | None = None,
        clamp_chunks: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        """Beam-search inference. Returns (scores [n, k], labels [n, k]).

        ``method`` picks the masked-matmul backend (see the table above the
        ``METHODS`` tuple; another name raises ``ValueError``); ``qt`` is
        the query-tile height of the grouped Pallas kernel (ignored by other
        methods). All methods return the same rankings (see the contract
        above ``METHODS``).

        ``init_parent_ids``/``init_scores`` (int32/f32 ``[n, b]``) start the
        search from an externally-computed beam instead of the root — the
        scatter–gather continuation path (``repro.index``): a router hands
        each label partition its surviving beam entries. ``clamp_chunks``
        parks out-of-range parents (id ≥ chunk count) on the last chunk —
        the phantom chunk :meth:`extract` appends — instead of relying on
        gather clamping, so masked beam entries score exactly ``NEG_INF``
        children and never alias a real chunk.
        """
        return _tree_infer(
            tuple(self.layers),
            self.n_cols,
            self.branching,
            self.d,
            x_idx,
            x_val,
            init_parent_ids,
            init_scores,
            beam=beam,
            topk=topk,
            method=check_method(method),
            score_mode=score_mode,
            qt=qt,
            clamp_chunks=clamp_chunks,
        )


def level_combined(
    layer: TreeLayerArrays,
    branching: int,
    d: int,
    x_idx: jax.Array,
    x_val: jax.Array,
    x_dense: jax.Array | None,
    parent_ids: jax.Array,     # int32 [n, b] chunk ids (already clamped)
    parent_scores: jax.Array,  # f32 [n, b]
    *,
    method: str,
    score_mode: str,
    qt: int = 8,
) -> jax.Array:
    """One level's *combined* child scores σ(logit) ⊗ parent — f32 [n, b, B].

    The single source of truth for per-level arithmetic: the in-tree beam
    search and the scatter–gather planner (:mod:`repro.index.planner`) both
    go through here, which is what makes a partition's owned rows
    bitwise-identical to the same rows scored through the full tree.
    """
    n, b_cur = parent_ids.shape
    block_q = jnp.repeat(jnp.arange(n, dtype=jnp.int32), b_cur)
    block_c = parent_ids.reshape(-1)
    if method == "mscm_pallas_grouped":
        from repro.kernels import ops  # local import: kernels are optional

        # Grouped path: chunk grouping, MXU-tiled matmul, and the σ⊗parent
        # epilogue all happen inside the kernel dispatch — the combined beam
        # scores are the only HBM round-trip per level.
        return ops.mscm_grouped_level(
            x_idx,
            x_val,
            d,
            layer.chunk_rows,
            layer.chunk_vals,
            block_q,
            block_c,
            parent_scores.reshape(-1),
            qt=qt,
            mode=score_mode,
        ).reshape(n, b_cur, branching)
    if method == "mscm_pallas_grouped_q":
        from repro.quant import kernels as qkernels  # local: tier is optional

        # Quantized grouped path: same device grouping and fused epilogue,
        # with the int8/fp8 chunk tile dequantized in-register against its
        # per-column scale row (layer is a QuantLayerArrays).
        return qkernels.mscm_grouped_q_level(
            x_idx,
            x_val,
            d,
            layer.chunk_rows,
            layer.chunk_vals,
            layer.chunk_scales,
            block_q,
            block_c,
            parent_scores.reshape(-1),
            qt=qt,
            mode=score_mode,
        ).reshape(n, b_cur, branching)
    # The masked product A = M ⊙ (X W) (paper eq. 6), then the epilogue.
    if method == "vanilla":
        logits = mscm_lib.vanilla_columns(
            x_idx, x_val, layer.col_rows, layer.col_vals, block_q, block_c,
            branching, d,
        )
    elif method == "mscm_dense":
        logits = mscm_lib.mscm_dense_lookup(
            x_dense, layer.chunk_rows, layer.chunk_vals, block_q, block_c
        )
    else:
        raise ValueError(f"unknown method {method!r}; choose from {METHODS}")
    return combine_scores(
        parent_scores, logits.reshape(n, b_cur, branching), score_mode
    )


def owned_level_combined(
    layer: TreeLayerArrays,
    branching: int,
    d: int,
    x_idx: jax.Array,
    x_val: jax.Array,
    x_dense: jax.Array | None,
    parent_ids: jax.Array,     # int32 [n, b] GLOBAL chunk ids at this level
    parent_scores: jax.Array,  # f32 [n, b]
    chunk_start: jax.Array,    # scalar: partition's first global chunk
    chunk_count: jax.Array,    # scalar: partition's real chunk count
    *,
    method: str,
    score_mode: str,
    qt: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """The :func:`level_combined` continuation API for partitioned slices.

    Localizes a *global* beam onto a partition's sliced layer — rows whose
    chunk falls in ``[chunk_start, chunk_start + chunk_count)`` are owned;
    everything else parks on the phantom chunk (index ``chunk_count``, the
    all-sentinel pad :meth:`XMRTree.extract` appends) and returns exactly
    ``NEG_INF`` — then scores one level through the same arithmetic as the
    in-tree traversal. Returns ``(combined [n, b, B], owned [n, b])``.

    This is the single continuation point both scatter–gather sync modes go
    through (``repro.index.planner``): the per-level exchange scores the
    canonical global beam here, and the pipelined mode scores its
    *speculative* local beam here — which is why a speculative row that
    survives the global select is bitwise what the full tree computes.
    ``chunk_start``/``chunk_count`` are meant to be traced so equal-shape
    partitions share one compilation.
    """
    owned = (parent_ids >= chunk_start) & (parent_ids < chunk_start + chunk_count)
    local_ids = jnp.where(owned, parent_ids - chunk_start, chunk_count)
    local_scores = jnp.where(owned, parent_scores, NEG_INF)
    combined = level_combined(
        layer, branching, d, x_idx, x_val, x_dense,
        local_ids.astype(jnp.int32), local_scores,
        method=method, score_mode=score_mode, qt=qt,
    )
    return jnp.where(owned[..., None], combined, NEG_INF), owned


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_cols", "branching", "d", "beam", "topk", "method", "score_mode",
        "qt", "clamp_chunks",
    ),
)
def _tree_infer(
    layers: Tuple[TreeLayerArrays, ...],
    n_cols: Tuple[int, ...],
    branching: Tuple[int, ...],
    d: int,
    x_idx: jax.Array,
    x_val: jax.Array,
    init_parent_ids: jax.Array | None = None,
    init_scores: jax.Array | None = None,
    *,
    beam: int,
    topk: int,
    method: str,
    score_mode: str,
    qt: int = 8,
    clamp_chunks: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    n = x_idx.shape[0]
    x_dense = (
        mscm_lib.scatter_dense(x_idx, x_val, d)
        if reads_dense_table(method) else None
    )

    if init_parent_ids is not None:
        # Continuation from an external beam (scatter–gather partitions).
        parent_ids = init_parent_ids.astype(jnp.int32)
        scores = init_scores.astype(jnp.float32)
    else:
        # Layer 1 is the root: prediction 1 (Alg. 1 line 3); its children
        # form chunk 0 of the first stored level.
        parent_ids = jnp.zeros((n, 1), jnp.int32)
        scores = (
            jnp.ones((n, 1), jnp.float32)
            if score_mode == "prod"
            else jnp.zeros((n, 1), jnp.float32)
        )
    for li, layer in enumerate(layers):
        chunk_ids = parent_ids
        if clamp_chunks:
            # Phantom beam entries (id ≥ real chunk count) park on the last
            # chunk — the all-sentinel phantom extract() appends, whose
            # logits are exactly 0 and whose children ids fall at/after the
            # local label count, so beam_select re-pins them to NEG_INF.
            chunk_ids = jnp.minimum(
                parent_ids, layer.chunk_rows.shape[0] - 1
            )
        is_last = li == len(layers) - 1
        next_b = min(topk if is_last else beam, n_cols[li])
        combined = level_combined(
            layer, branching[li], d, x_idx, x_val, x_dense, chunk_ids,
            scores, method=method, score_mode=score_mode, qt=qt,
        )
        parent_ids, scores = beam_select(
            chunk_ids, combined, n_cols[li], next_b
        )
        if method in ("mscm_pallas_grouped", "mscm_pallas_grouped_q") and not is_last:
            # Keep the beam id-ascending: children of a sorted beam are a
            # concatenation of sorted runs, so level l+1's block list
            # inherits level l's chunk-major discipline and the global
            # grouping argsort only merges across queries. Selection is
            # canonical (beam_select), so reordering cannot change results.
            perm = jnp.argsort(parent_ids, axis=1)
            parent_ids = jnp.take_along_axis(parent_ids, perm, axis=1)
            scores = jnp.take_along_axis(scores, perm, axis=1)
    return scores, parent_ids
