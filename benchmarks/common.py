"""Shared benchmark utilities: tree builders + timing harness."""

from __future__ import annotations

import time
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import XMRTree
from repro.data.xmr_data import XMRShape, benchmark_queries
from repro.sparse import CSC, random_sparse_csc
from repro.trees.cluster import build_tree_structure


def build_benchmark_weights(shape: XMRShape, branching: int,
                            rng: np.random.Generator,
                            *, upper_nnz: int = 64,
                            sibling_overlap: float = 0.8) -> List[CSC]:
    """Random per-level CSC weights at the dataset's dimensions (latency
    depends only on the sparsity structure, not learned values — see
    data/xmr_data.py)."""
    struct = build_tree_structure(shape.L, branching)
    weights = []
    for size in struct.level_sizes:
        nnz = shape.col_nnz if size == struct.level_sizes[-1] else upper_nnz
        weights.append(
            random_sparse_csc(shape.d, size, nnz, rng,
                              sibling_groups=branching,
                              sibling_overlap=sibling_overlap)
        )
    return weights


def build_benchmark_tree(shape: XMRShape, branching: int,
                         rng: np.random.Generator, **kw) -> XMRTree:
    """:func:`build_benchmark_weights` packed into a served tree."""
    return XMRTree.from_weight_matrices(
        build_benchmark_weights(shape, branching, rng, **kw), branching
    )


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            **kwargs) -> float:
    """Median wall seconds per call (blocking on outputs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def ell_queries(shape: XMRShape, n: int, rng: np.random.Generator,
                width: int | None = None):
    x = benchmark_queries(shape, n, rng)
    xi, xv = x.to_ell(width)
    return jnp.asarray(xi), jnp.asarray(xv)


def csv_line(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"
