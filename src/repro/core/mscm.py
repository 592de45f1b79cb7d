"""Masked Sparse Chunk Multiplication — JAX implementations (paper §4).

Evaluates the masked product  A = M ⊙ (X · W)  where the mask nonzeros come
in contiguous width-B blocks, one per (query, surviving-parent) beam pair.
The active blocks are given as parallel index vectors

    block_q : int32 [A]   query row of each block
    block_c : int32 [A]   chunk (parent) id of each block

and the result is the dense [A, B] stack of block values — static shapes,
no dynamic sparsity anywhere.

The two XLA paths of ``core.tree.METHODS`` (paper §4, TPU-adapted):

* ``mscm_dense_lookup``  — dense-lookup analogue: queries pre-scattered into a
  dense [n, d+1] table; per-block gather at the chunk's ELL rows + one
  [R]·[B,R]ᵀ contraction. One traversal *per chunk*.
* ``vanilla_columns``    — the non-MSCM baseline (paper Alg. 4): each of the
  B columns of the block intersects with the query *independently* (per-column
  ELL layout). Same result, B× the traversal.

The chip's path, the grouped Pallas kernel, lives in ``repro.kernels`` and
intersects the ELL queries with the chunk rows on the vector unit. Hash-map /
marching pointers do not transfer to TPU (no pointer-chasing);
``repro.kernels.ref`` keeps a marching-pointer oracle for tests.

All functions are jit-friendly and differentiable in ``vals``/``x_val``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Matmul precision of every f32 dot on the serving path. A TPU's default
#: rounds f32 operands to bf16 (~1e-3 relative error); HIGHEST keeps them
#: f32, which the exactness contract (core.tree.METHODS) needs.
F32 = jax.lax.Precision.HIGHEST


def scatter_dense(x_idx: jax.Array, x_val: jax.Array, d: int) -> jax.Array:
    """Scatter ELL queries into a dense [n, d+1] lookup table.

    The trailing slot (index d) is the sentinel target and always holds 0,
    so gathers at padded chunk rows contribute nothing. This is the TPU
    analogue of the paper's *dense lookup* iterator: the scatter cost is paid
    once per query and amortized over every chunk it meets (paper §4 item 4).
    """
    n = x_idx.shape[0]
    out = jnp.zeros((n, d + 1), dtype=x_val.dtype)
    return out.at[jnp.arange(n)[:, None], x_idx].add(x_val, mode="drop")


def mscm_dense_lookup(
    x_dense: jax.Array,   # f32 [n, d+1]
    rows: jax.Array,      # int32 [C, R]
    vals: jax.Array,      # f32 [C, B, R]
    block_q: jax.Array,   # int32 [A]
    block_c: jax.Array,   # int32 [A]
) -> jax.Array:
    """Dense-lookup MSCM: gather query values at chunk rows, contract."""
    r = rows[block_c]                                   # [A, R]
    xg = x_dense[block_q[:, None], r]                   # [A, R]  (gather)
    return jnp.einsum("ar,abr->ab", xg, vals[block_c], precision=F32)  # [A, B]


def vanilla_columns(
    x_idx: jax.Array,     # int32 [n, Q]
    x_val: jax.Array,     # f32 [n, Q]
    col_rows: jax.Array,  # int32 [L, Rc] per-column ELL
    col_vals: jax.Array,  # f32 [L, Rc]
    block_q: jax.Array,   # int32 [A]
    block_c: jax.Array,   # int32 [A]
    branching: int,
    d: int,
) -> jax.Array:
    """Non-MSCM baseline (paper Alg. 4): per-column sparse dot products.

    Expands each block into its B columns and intersects each column's row
    list with the query separately — B independent traversals per block.
    The same result as the MSCM paths up to summation order.
    """
    a = block_q.shape[0]
    cols = block_c[:, None] * branching + jnp.arange(branching)[None, :]  # [A, B]
    xi = x_idx[block_q]                                  # [A, Q]
    xv = x_val[block_q]
    cr = col_rows[cols]                                  # [A, B, Rc]
    cv = col_vals[cols]                                  # [A, B, Rc]
    q = xi.shape[1]

    def one_col(xi_a, xv_a, cr_ab, cv_ab):
        pos = jnp.searchsorted(xi_a, cr_ab, side="left")
        pos_c = jnp.minimum(pos, q - 1)
        hit = (xi_a[pos_c] == cr_ab) & (cr_ab < d)
        return jnp.sum(jnp.where(hit, xv_a[pos_c] * cv_ab, 0.0))

    per_block = jax.vmap(
        lambda xi_a, xv_a, cr_a, cv_a: jax.vmap(lambda r, v: one_col(xi_a, xv_a, r, v))(cr_a, cv_a)
    )
    return per_block(xi, xv, cr, cv)                     # [A, B]


# ---------------------------------------------------------------------------
# Cost model counters (paper Table 6) — host-side, used by tests/benchmarks.
# ---------------------------------------------------------------------------

def iterator_cost(
    method: str,
    nnz_x: int,
    nnz_k: int,
    *,
    n_queries: int = 1,
    d: int = 0,
    hash_cost: float = 1.5,
) -> float:
    """Per-query traversal cost of one (query, chunk) intersection.

    Mirrors paper Table 6:
      marching    O(nnz_x + nnz_K)
      binsearch   O(min · log max)
      hash        O(h · nnz_x)
      dense       O(nnz_x + nnz_K / n)   (scatter amortized over the batch)
    """
    if method == "marching":
        return nnz_x + nnz_k
    if method in ("binsearch", "searchsorted"):
        lo, hi = sorted((max(nnz_x, 1), max(nnz_k, 1)))
        return lo * float(np.log2(max(hi, 2)))
    if method == "hash":
        return hash_cost * nnz_x
    if method in ("dense", "dense_lookup"):
        return nnz_k + nnz_x / max(n_queries, 1)
    raise ValueError(f"unknown iterator {method}")


def chunk_vs_column_traversals(
    chunk_R: int, col_nnz: np.ndarray, branching: int
) -> Tuple[int, int]:
    """(MSCM traversal length, vanilla traversal length) for one block —
    quantifies paper Item 1/2: once-per-chunk vs once-per-column."""
    return int(chunk_R), int(col_nnz[:branching].sum())
