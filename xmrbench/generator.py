"""The one traffic generator: reads a mix file's parameters, never code.

A mix (``xmrbench/traffic/<mix>.json``) has three parts:

``queries``  how each query is drawn. ``model: "label_path"`` (a copy of
             the label-path model of ``chip_smoke.path_queries``, made
             whole-array): a target label is drawn (``targets``, below),
             ``path_share`` of the query's ``query_nnz`` features come
             from the supports of the target's ancestor columns, split
             evenly over the levels, and the rest is Zipf noise over
             ``[0, d)`` with exponent ``zipf_a``, drawn until the query
             holds exactly ``query_nnz`` distinct ids (the configuration's
             published average); values are ``|N(0, 1)| + value_offset``.
             ``targets``: ``{"kind": "uniform"}`` over the labels, or
             ``{"kind": "zipf", "a": a}``: label ranks Zipf(``a``) over a
             permutation of the labels drawn from the seed, so popular
             labels lie spread over the tree and share its chunks.
``pool``     how many distinct queries a run draws; requests cycle
             through them.
``arrivals`` how requests are offered (the workload's ``serve.entry``
             must take them, see ``ENTRIES``):
             ``{"kind": "blocks", "block": n}``: closed loop, one call of
             ``n`` queries after another.
             ``{"kind": "serial"}``: closed loop, one query at a time, the
             next sent when the last is answered.
             ``{"kind": "poisson"}``: open loop at the workload's
             ``rate_qps``; a run of ``seconds`` sends exactly
             ``round(rate_qps * seconds)`` requests at uniformly drawn,
             sorted times (a Poisson process given its count), so every
             seed sends the same amount of work.
             ``{"kind": "bursts", "on_s": a, "off_s": b, "on_x": x,
             "off_x": y}``: open loop in alternating phases, ``a`` seconds
             at ``x`` times ``rate_qps`` then ``b`` seconds at ``y``
             times it; each phase sends a fixed count, as ``poisson``
             does.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

#: The serving entry that takes each kind of arrivals.
ENTRIES = {"blocks": "serve_batch", "serial": "serve_online",
           "poisson": "microbatcher", "bursts": "microbatcher"}


def target_labels(rng: np.random.Generator, n_labels: int, n: int,
                  targets: dict) -> np.ndarray:
    """The target label of each of ``n`` queries."""
    kind = targets.get("kind")
    if kind == "uniform":
        return rng.integers(0, n_labels, size=n)
    if kind == "zipf":
        ranks = (rng.zipf(float(targets["a"]), size=n) - 1) % n_labels
        return rng.permutation(n_labels)[ranks]
    raise ValueError(f"unknown targets {targets}")


def first_distinct(ids: np.ndarray) -> np.ndarray:
    """Mask of the first occurrence of each id in each row of ``ids``."""
    order = np.argsort(ids, axis=1, kind="stable")
    s = np.take_along_axis(ids, order, axis=1)
    first = np.ones(s.shape, bool)
    first[:, 1:] = s[:, 1:] != s[:, :-1]
    keep = np.empty_like(first)
    np.put_along_axis(keep, order, first, axis=1)
    return keep


def label_path_queries(rng: np.random.Generator, levels: Sequence[Tuple],
                       branching: int, n: int, query_nnz: int,
                       spec: dict):
    """CSR arrays ``(indptr, indices, data)`` of ``n`` queries, each with
    exactly ``query_nnz`` distinct ids.

    ``levels`` are the per-level CSC tuples ``(indptr, indices, data,
    (d, n_cols))`` from the top stored level to the leaves.
    """
    if spec.get("model") != "label_path":
        raise ValueError(f"unknown query model {spec}")
    d, n_labels = levels[-1][3]
    if query_nnz > d:
        raise ValueError(f"query_nnz {query_nnz} > d {d}")
    depth = len(levels)
    per_level = int(query_nnz * float(spec["path_share"])) // depth
    leaf = target_labels(rng, n_labels, n, spec["targets"])
    feats = []
    for li, (indptr, indices, _, _) in enumerate(levels):
        col = leaf // branching ** (depth - 1 - li)
        nnz = indptr[col + 1] - indptr[col]
        k = int(min(per_level, nnz.min()))
        width = int(nnz.max())
        keys = rng.random((n, width))
        keys[np.arange(width)[None, :] >= nnz[:, None]] = np.inf
        pos = np.argpartition(keys, k - 1, axis=1)[:, :k] if k else \
            np.zeros((n, 0), np.int64)
        feats.append(indices[indptr[col][:, None] + pos])
    # Zipf noise fills each query up to query_nnz distinct ids: draws that
    # repeat an id already in the query are passed over, in draw order.
    ids = np.concatenate(feats, axis=1).astype(np.int64)
    block = 2 * max(1, query_nnz - ids.shape[1])
    zipf_a = float(spec["zipf_a"])
    while True:
        keep = first_distinct(ids)
        kept = np.cumsum(keep, axis=1)
        if (kept[:, -1] >= query_nnz).all():
            break
        noise = (rng.zipf(zipf_a, size=(n, block)) - 1) % d
        ids = np.concatenate([ids, noise], axis=1)
    keep &= kept <= query_nnz
    ids = np.sort(ids[keep].reshape(n, query_nnz), axis=1)
    indptr = np.arange(n + 1, dtype=np.int64) * query_nnz
    indices = ids.reshape(-1).astype(np.int32)
    data = (np.abs(rng.standard_normal(indices.size)) +
            float(spec["value_offset"])).astype(np.float32)
    return indptr, indices, data


def _uniform_times(rng: np.random.Generator, rate_qps: float, lo: float,
                   hi: float) -> np.ndarray:
    return rng.uniform(lo, hi, size=int(round(rate_qps * (hi - lo))))


def arrival_offsets(rng: np.random.Generator, arrivals: dict,
                    rate_qps: float, seconds: float) -> np.ndarray:
    """Sorted send times in ``[0, seconds)`` of an open-loop mix."""
    kind = arrivals.get("kind")
    if kind == "poisson":
        return np.sort(_uniform_times(rng, rate_qps, 0.0, seconds))
    if kind != "bursts":
        raise ValueError(f"{kind!r} arrivals have no send times")
    phases = [(float(arrivals["on_s"]), float(arrivals["on_x"])),
              (float(arrivals["off_s"]), float(arrivals["off_x"]))]
    if min(p[0] for p in phases) <= 0:
        raise ValueError(f"burst phases must be positive: {arrivals}")
    times, t, i = [], 0.0, 0
    while t < seconds:
        length, x = phases[i % 2]
        end = min(seconds, t + length)
        times.append(_uniform_times(rng, rate_qps * x, t, end))
        t, i = end, i + 1
    return np.sort(np.concatenate(times))
