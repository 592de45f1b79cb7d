"""Distributed XMR inference: queries × label-space sharding (shard_map).

Maps the paper's §6.1 parallelism onto the production mesh:

* ``data`` axis  — queries shard embarrassingly (the paper's OpenMP claim);
* ``model`` axis — the LEAF level's chunks shard by label range (at 100M
  labels the leaf weight tensor is the model; upper levels are ≤ 1/B the
  size and replicate).

Each (query, surviving-parent) block is owned by exactly one model shard
(chunk ranges are contiguous), so every shard scores its local blocks with
the same MSCM kernels, takes a local top-k, and a candidate all-gather +
global top-k completes the beam — the standard distributed-retrieval
reduction, with traffic k·shards candidates per query instead of the full
score row.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mscm as mscm_lib
from repro.core.beam import NEG_INF, beam_step, topk_canonical
from repro.core.tree import TreeLayerArrays, XMRTree


def shard_leaf_level(tree: XMRTree, mesh: Mesh):
    """Device-put the leaf level sharded over 'model', upper levels replicated."""
    leaf = tree.layers[-1]
    rep = NamedSharding(mesh, P())
    sharded = TreeLayerArrays(
        chunk_rows=jax.device_put(leaf.chunk_rows, NamedSharding(mesh, P("model", None))),
        chunk_vals=jax.device_put(leaf.chunk_vals, NamedSharding(mesh, P("model", None, None))),
        col_rows=jax.device_put(leaf.col_rows, rep),
        col_vals=jax.device_put(leaf.col_vals, rep),
    )
    upper = [
        jax.tree.map(lambda a: jax.device_put(a, rep), l) for l in tree.layers[:-1]
    ]
    return upper, sharded


def sharded_infer(
    tree: XMRTree,
    upper_layers,
    leaf_sharded: TreeLayerArrays,
    x_idx: jax.Array,
    x_val: jax.Array,
    mesh: Mesh,
    *,
    beam: int = 10,
    topk: int = 10,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed Algorithm 1. Queries sharded over 'data', leaf chunks over
    'model'. Returns (scores [n, k], leaf ids [n, k]) fully replicated."""
    d = tree.d
    n_cols = tree.n_cols
    branching = tree.branching
    n_total = x_idx.shape[0]

    upper_flat, upper_tree = jax.tree_util.tree_flatten(
        [(l.chunk_rows, l.chunk_vals) for l in upper_layers]
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P("data", None), P("data", None),
            P("model", None), P("model", None, None),
            tuple(P() for _ in upper_flat),
        ),
        out_specs=(P("data", None), P("data", None)),
        check_vma=False,
    )
    def run(xi, xv, leaf_rows, leaf_vals, upper_arrays):
        upper_local = jax.tree_util.tree_unflatten(upper_tree, list(upper_arrays))
        n = xi.shape[0]
        xd = mscm_lib.scatter_dense(xi, xv, d)
        parent = jnp.zeros((n, 1), jnp.int32)
        scores = jnp.ones((n, 1), jnp.float32)
        # upper levels: replicated weights, local queries
        for li, (rows_l, vals_l) in enumerate(upper_local):
            bc = parent.shape[1]
            bq = jnp.repeat(jnp.arange(n, dtype=jnp.int32), bc)
            logits = mscm_lib.mscm_dense_lookup(
                xd, rows_l, vals_l, bq, parent.reshape(-1)
            ).reshape(n, bc, branching[li])
            nb = min(beam, n_cols[li])
            parent, scores = beam_step(parent, scores, logits, n_cols[li], nb)

        # leaf level: chunk-range ownership on the model axis
        li = len(upper_local)
        my = jax.lax.axis_index("model")
        c_local = leaf_vals.shape[0]  # per-shard chunk count
        bc = parent.shape[1]
        bq = jnp.repeat(jnp.arange(n, dtype=jnp.int32), bc)
        flat_parent = parent.reshape(-1)
        owner = flat_parent // c_local
        local_c = jnp.clip(flat_parent - my * c_local, 0, c_local - 1)
        logits = mscm_lib.mscm_dense_lookup(
            xd, leaf_rows, leaf_vals, bq, local_c
        ).reshape(n, bc, branching[li])
        mine = (owner == my).reshape(n, bc, 1)
        child = flat_parent.reshape(n, bc, 1) * branching[li] + jnp.arange(branching[li])
        comb = jnp.where(
            mine & (child < n_cols[li]),
            jax.nn.sigmoid(logits) * scores[..., None],
            NEG_INF,
        )
        k = min(topk, n_cols[li])
        # canonical (score desc, id asc) local top-k — same tie-break as
        # beam_select, so the shard boundary can never reorder ties
        loc_i, loc_s = topk_canonical(
            comb.reshape(n, -1), child.reshape(n, -1), k
        )
        # candidate all-gather over the label shards + canonical global top-k
        all_s = jax.lax.all_gather(loc_s, "model", axis=1).reshape(n, -1)
        all_i = jax.lax.all_gather(loc_i, "model", axis=1).reshape(n, -1)
        g_i, g_s = topk_canonical(all_s, all_i, k)
        return g_s, g_i

    return run(x_idx, x_val, leaf_sharded.chunk_rows, leaf_sharded.chunk_vals,
               tuple(upper_flat))
