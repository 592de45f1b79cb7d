"""Pure-jnp / numpy oracles for the MSCM kernels.

``mscm_ref`` is the dense-algebra ground truth: reconstruct W from the chunk
tiles, evaluate the full product X·W, and read out the masked blocks. Every
MSCM variant (JAX and Pallas) must match it.

``block_ref_marching`` is a numpy marching-pointer implementation of the
paper's Algorithm 2 (the one iterator with no TPU analogue) — kept as an
independent scalar oracle for property tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mscm import F32


def mscm_ref(
    x_dense: jax.Array,   # f32 [n, d+1] (dense queries incl. sentinel slot)
    rows: jax.Array,      # int32 [C, R] sentinel-padded
    vals: jax.Array,      # f32 [C, R, B]
    block_q: jax.Array,   # int32 [A]
    block_c: jax.Array,   # int32 [A]
) -> jax.Array:
    """Dense oracle: A[a] = (x[block_q[a]] · W)[block_c[a]·B : +B]."""
    c, r, b = vals.shape
    d_plus = x_dense.shape[1]
    # Scatter chunk tiles into the dense [d+1, C*B] weight matrix. Sentinel
    # rows (== d) land in the zero slot of x_dense, contributing nothing.
    w = jnp.zeros((d_plus, c * b), dtype=vals.dtype)
    col_ids = (jnp.arange(c)[:, None, None] * b + jnp.arange(b)[None, None, :])
    col_ids = jnp.broadcast_to(col_ids, (c, r, b))
    row_ids = jnp.broadcast_to(rows[:, :, None], (c, r, b))
    w = w.at[row_ids.reshape(-1), col_ids.reshape(-1)].add(vals.reshape(-1))
    w = w.at[d_plus - 1, :].set(0.0)  # sentinel row carries no weight
    full = jnp.matmul(x_dense, w, precision=F32)              # [n, C*B]
    cols = block_c[:, None] * b + jnp.arange(b)[None, :]      # [A, B]
    return full[block_q[:, None], cols]


def block_ref_marching(
    x_idx: np.ndarray,     # int32 [nnz_x] sorted query support
    x_val: np.ndarray,     # f32 [nnz_x]
    chunk_rows: np.ndarray,  # int32 [R] sentinel-padded, sorted
    chunk_vals: np.ndarray,  # f32 [R, B]
    d: int,
) -> np.ndarray:
    """Paper Algorithm 2 with the marching-pointer iterator (numpy scalar)."""
    b = chunk_vals.shape[1]
    z = np.zeros(b, dtype=np.float64)
    ix, ik = 0, 0
    nx, nk = len(x_idx), len(chunk_rows)
    while ix < nx and ik < nk:
        jx, jk = int(x_idx[ix]), int(chunk_rows[ik])
        if jx >= d or jk >= d:
            break
        if jx == jk:
            z += float(x_val[ix]) * chunk_vals[ik].astype(np.float64)
            ix += 1
            ik += 1
        elif jx < jk:
            ix += 1
        else:
            ik += 1
    return z.astype(np.float32)
