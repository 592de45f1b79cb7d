"""Batched XMR serving engine.

Implements the paper's two production settings (§3.2):
* **batch** — a matrix of queries served in one shot;
* **online** — queries served one-by-one (batch size 1).

The engine owns jit-cache hygiene (batch sizes are bucketed to powers of two,
query nnz padded to a fixed ELL width) and records wall-clock statistics in
the form the paper reports (avg / P95 / P99, Table 4) — per-query samples
for the online setting, amortized call averages for the batch setting, kept
as distinct series so percentiles stay honest.

Query marshalling is the vectorized CSR→ELL path in
:func:`repro.sparse.csr.rows_to_ell`; ``serve_batch`` double-buffers so host
marshalling of chunk *i+1* overlaps device execution of chunk *i* (JAX
dispatch is asynchronous — we only block when the *previous* chunk's results
are consumed). The async micro-batching front-end lives in
:mod:`repro.serving.batcher`.

Sharded dispatch (``ServeConfig(shards=N)``): the tree is replicated over a
1-D data mesh of N local devices (:func:`repro.distributed.sharding
.replica_mesh`) and every dispatched bucket's batch dim is split across the
replicas, so one formed micro-batch occupies all N devices instead of
serializing on one. Per-query arithmetic is untouched by the split —
results stay bitwise-identical to single-device serving (pinned by
tests/test_sharded_serving.py).

Partitioned dispatch (``ServeConfig(partitions=P)``): the tree is split into
P label-contiguous sub-trees over a ``("data", "model")`` mesh
(:mod:`repro.index`) and every dispatch runs the scatter-gather planner —
per-device model bytes shrink ~1/P while results stay bitwise-identical in
the ``partition_sync="level"`` (default) and ``"pipelined"`` modes;
``"pipelined"`` overlaps each level's beam exchange with the next level's
MSCM matmul via speculative expansion, and ``beam_cache=N`` adds the
hot-beam LRU that skips partitions owning no surviving router-beam row.
Composes with ``shards=N``: model-parallel partitions x data-parallel
replicas behind one batcher.
"""

from __future__ import annotations

import itertools
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import XMRTree
from repro.serving import spans
from repro.serving.config import (
    AdmissionConfig,
    PartitionConfig,
    QuantConfig,
    ServeConfig,
)
from repro.serving.metrics import LatencyStats
from repro.serving.slo import BeamTier, resolve_tiers
from repro.sparse.csr import CSR, rows_to_ell

__all__ = [
    "AdmissionConfig",
    "PartitionConfig",
    "QuantConfig",
    "ServeConfig",
    "XMRServingEngine",
    "resolve_method",
]


def resolve_method(method: str) -> str:
    """Resolve ``"auto"`` to the best batch method for the active backend.

    On TPU that is the device-grouped MXU-tiled Pallas kernel (the paper's
    batch-mode fast path, fully inside the ``_tree_infer`` jit); elsewhere
    the dense-lookup einsum path — Pallas interpret mode is for validation,
    not speed.
    """
    if method != "auto":
        return method
    return (
        "mscm_pallas_grouped"
        if jax.default_backend() == "tpu"
        else "mscm_dense"
    )


def _bucket(n: int, max_batch: int) -> int:
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


class XMRServingEngine:
    def __init__(self, tree: XMRTree, config: ServeConfig | None = None,
                 label_perm: Optional[np.ndarray] = None):
        self.config = config or ServeConfig()
        self.method = resolve_method(self.config.method)
        qc = self.config.quant
        if qc.tier != "exact":
            # Compressed tiers store int8/fp8 chunk tiles + scale rows; the
            # quantized grouped kernel is the only method that can read
            # them. "auto" resolves there; an explicit exact method is a
            # config contradiction, not something to silently override.
            if self.config.method not in ("auto", "mscm_pallas_grouped_q"):
                raise ValueError(
                    f"quant tier {qc.tier!r} serves via "
                    f"method='mscm_pallas_grouped_q'; got explicit "
                    f"method={self.config.method!r}"
                )
            self.method = "mscm_pallas_grouped_q"
        # Adaptive beam-tier ladder (tier 0 = the configured full beam; a
        # 1-tuple unless slo.target_p99_ms is set). Degraded tiers must
        # reach the same result width as the full beam or QueryResult
        # shapes would change per batch — validate against the *original*
        # tree geometry before any quantize/partition reassignment below.
        self.tiers: Tuple[BeamTier, ...] = resolve_tiers(self.config)
        if len(self.tiers) > 1:
            from repro.index.planner import reference_topk_width

            c = self.config
            full_w = reference_topk_width(
                tree.n_cols, tree.branching, c.beam, c.topk
            )
            for t in self.tiers[1:]:
                w = reference_topk_width(
                    tree.n_cols, tree.branching, t.beam, c.topk
                )
                if w != full_w:
                    raise ValueError(
                        f"beam tier {t.beam} yields top-k width {w} != "
                        f"full-beam width {full_w}; widen the tier or "
                        f"raise slo min_beam"
                    )
        self.label_perm = label_perm  # leaf position -> original label id
        self.stats = LatencyStats()
        self._dispatch_ids = itertools.count()
        self.mesh = None
        self._batch_sharding = None
        self.index = None
        self.placement = None
        self.planner = None
        shards = self.config.shards
        if shards > 1 and shards & (shards - 1):
            raise ValueError(
                f"shards={shards} must be a power of two (buckets are)"
            )
        if shards > self.config.max_batch:
            raise ValueError(
                f"shards={shards} exceeds max_batch={self.config.max_batch}"
            )
        if qc.tier != "exact" and self.config.partition.partitions == 1:
            # Unpartitioned compressed serving: quantize the whole tree (the
            # QuantizedTree rides the same device_put/infer machinery, so
            # the shards>1 replication below works unchanged).
            from repro.quant import quantize_tree

            tree = quantize_tree(
                tree, tier=qc.tier, prune_keep=qc.prune_keep
            )
        if self.config.partition.partitions > 1:
            # Label-partitioned dispatch: the tree is cut into P sub-trees
            # placed over a ("data", "model") mesh; every _run goes through
            # the scatter-gather planner (model-parallel x data-parallel,
            # bitwise-identical in the default "level" sync mode).
            from repro.index import ScatterGatherPlanner, partition_tree, place

            c, pc = self.config, self.config.partition
            self.index = partition_tree(
                tree, pc.partitions, level=pc.partition_level
            )
            if qc.tier != "exact":
                # Quantize per partition *after* the cut: the router head
                # stays exact f32 (its beam feeds every partition) and the
                # manifest's memory_bytes/content_hash describe the
                # compressed bytes placement actually balances.
                from repro.quant import quantize_index

                self.index = quantize_index(
                    self.index, tier=qc.tier, prune_keep=qc.prune_keep
                )
            self.placement = place(self.index, shards=shards)
            self.planner = ScatterGatherPlanner(
                self.index,
                beam=c.beam,
                topk=c.topk,
                method=self.method,
                score_mode=c.score_mode,
                qt=c.qt,
                sync=pc.partition_sync,
                placement=self.placement,
                cache_entries=pc.beam_cache,
            )
            self.mesh = self.placement.mesh
        elif shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.distributed.sharding import replica_mesh

            self.mesh = replica_mesh(shards)
            # Replicate the tree once; every dispatch then splits its batch
            # dim over the mesh's data axis.
            tree = tree.device_put(NamedSharding(self.mesh, P()))
            self._batch_sharding = NamedSharding(self.mesh, P("data", None))
        self.tree = tree

    # -- query marshalling --------------------------------------------------
    def next_dispatch_id(self) -> int:
        """The next engine-wide dispatch sequence number, which tags the
        dispatch's host spans (:mod:`repro.serving.spans`)."""
        return next(self._dispatch_ids)

    def marshal_rows(self, queries: CSR, rows: np.ndarray, bucket: int,
                     *, dispatch: int = -1) -> Tuple[jax.Array, jax.Array]:
        """Vectorized ELL marshalling padded up to a jit bucket.

        Padding rows use the sentinel index ``d`` and value 0, i.e. empty
        queries — the bucket tail is sliced off by the caller. ``dispatch``
        tags the marshal span (-1 outside a dispatch).
        """
        with spans.span(spans.MARSHAL, dispatch, rows=len(rows),
                        bucket=bucket):
            w = self.config.ell_width
            d = queries.shape[1]
            idx, val = rows_to_ell(queries, rows, w)
            if bucket > len(rows):
                pad = bucket - len(rows)
                idx = np.concatenate([idx, np.full((pad, w), d, np.int32)])
                val = np.concatenate([val, np.zeros((pad, w), np.float32)])
            return jnp.asarray(idx), jnp.asarray(val)

    def bucket_for(self, n: int) -> int:
        """Power-of-two jit bucket for ``n`` queries.

        Never below ``shards`` so a sharded dispatch always splits evenly
        over the mesh (both are powers of two).
        """
        return max(_bucket(n, self.config.max_batch), self.config.shards)

    def bucket_key(self, n: int, tier: int = 0) -> Tuple[int, int]:
        """jit-cache key for a dispatch: ``(bucket, beam_tier)``.

        Every (power-of-two bucket, tier) pair compiles its own
        ``_tree_infer`` entry — both coordinates are bounded static sets
        (buckets by ``max_batch``, tiers by the SLO ladder), so the cache
        stays XMR003-clean and ``warmup_buckets`` can enumerate it fully.
        """
        return (self.bucket_for(n), int(tier))

    def _run(self, xi: jax.Array, xv: jax.Array, tier: int = 0):
        c = self.config
        t = self.tiers[tier]
        if self.planner is not None:
            # Scatter-gather over the label partitions; the planner owns all
            # device placement (per-partition batch sharding included). The
            # tier's beam/qt ride as per-call overrides only when degraded,
            # so the tier-0 path (and its wire traffic) is byte-identical
            # to an engine without an SLO configured.
            if tier:
                return self.planner.infer(xi, xv, beam=t.beam, qt=t.qt)
            return self.planner.infer(xi, xv)
        if self._batch_sharding is not None:
            xi = jax.device_put(xi, self._batch_sharding)
            xv = jax.device_put(xv, self._batch_sharding)
        return self.tree.infer(
            xi, xv, beam=t.beam, topk=c.topk, method=self.method,
            score_mode=c.score_mode, qt=t.qt,
        )

    # -- serving modes --------------------------------------------------
    def warmup(self, d: int, batch_sizes: Sequence[int] = (1,),
               tier: int = 0) -> None:
        for b in batch_sizes:
            bb = self.bucket_for(b)
            xi = jnp.full((bb, self.config.ell_width), d, jnp.int32)
            xv = jnp.zeros((bb, self.config.ell_width), jnp.float32)
            s, l = self._run(xi, xv, tier=tier)
            jax.block_until_ready((s, l))

    def warmup_buckets(self, d: int, max_batch: int,
                       tiers: Optional[Sequence[int]] = None) -> None:
        """Warm every jit bucket a batcher capped at ``max_batch`` can form.

        Covers all power-of-two buckets up to ``bucket_for(max_batch)``
        inclusive — note the cap itself need not be a power of two (a
        size-triggered batch of 24 pads to bucket 32), and sharded engines
        never form a bucket below ``shards``. With an SLO ladder, every
        ``(bucket, tier)`` cache key is warmed (the full cross product is
        bounded), so a degraded dispatch never pays a live compile.
        """
        sizes, b = [], self.config.shards or 1
        target = self.bucket_for(max_batch)
        while b <= target:
            sizes.append(b)
            b *= 2
        for tier in tiers if tiers is not None else range(len(self.tiers)):
            self.warmup(d, sizes, tier=tier)

    def serve_batch(self, queries: CSR) -> Tuple[np.ndarray, np.ndarray]:
        """Batch setting: all queries at once (bucketed into max_batch chunks).

        Double-buffered: chunk *i+1* is marshalled on the host while the
        device executes chunk *i*. Because chunks overlap, per-chunk wall
        times are not individually meaningful — one amortized per-query
        average is recorded per call, in the stats' *amortized* series so it
        never pollutes the per-query percentile panel.
        """
        n = queries.shape[0]
        out_s, out_l = [], []

        def finalize(pending) -> None:
            s, l, count, dispatch = pending
            with spans.span(spans.WAIT, dispatch):
                jax.block_until_ready((s, l))
            with spans.span(spans.FETCH, dispatch):
                out_s.append(np.asarray(s)[:count])
                out_l.append(self._map_labels(np.asarray(l)[:count]))

        t_start = time.perf_counter()
        pending = None
        i = 0
        while i < n:
            dispatch = self.next_dispatch_id()
            count = min(self.config.max_batch, n - i)
            bucket = self.bucket_for(count)
            xi, xv = self.marshal_rows(queries, np.arange(i, i + count), bucket,
                                       dispatch=dispatch)
            with spans.span(spans.DISPATCH, dispatch, bucket=bucket, tier=0):
                s, l = self._run(xi, xv)  # async dispatch
            if pending is not None:
                finalize(pending)
            pending = (s, l, count, dispatch)
            i += count
        if pending is not None:
            finalize(pending)
        self.stats.record_amortized(time.perf_counter() - t_start, n)
        return np.concatenate(out_s), np.concatenate(out_l)

    def serve_online(self, queries: CSR, limit: int | None = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Online setting: one query at a time, per-query latency recorded."""
        n = queries.shape[0] if limit is None else min(limit, queries.shape[0])
        out_s, out_l = [], []
        bucket = self.bucket_for(1)  # 1 unsharded; >= shards on a mesh
        for i in range(n):
            dispatch = self.next_dispatch_id()
            xi, xv = self.marshal_rows(queries, np.arange(i, i + 1), bucket,
                                       dispatch=dispatch)
            t0 = time.perf_counter()
            with spans.span(spans.DISPATCH, dispatch, bucket=bucket, tier=0):
                s, l = self._run(xi, xv)
            with spans.span(spans.WAIT, dispatch):
                jax.block_until_ready((s, l))
            self.stats.record(time.perf_counter() - t0)
            with spans.span(spans.FETCH, dispatch):
                out_s.append(np.asarray(s)[0])
                out_l.append(self._map_labels(np.asarray(l)[0]))
        return np.stack(out_s), np.stack(out_l)

    def _map_labels(self, leaves: np.ndarray) -> np.ndarray:
        if self.label_perm is None:
            return leaves
        return self.label_perm[leaves]

    def partition_hit_counts(self, leaves: np.ndarray) -> Optional[np.ndarray]:
        """Per-partition result share for a batch of *raw* leaf ids
        (pre-``label_perm``); None when serving unpartitioned."""
        if self.planner is None:
            return None
        return self.planner.hit_counts(leaves)

    def beam_cache_stats(self) -> Optional[dict]:
        """Cumulative hot-beam cache accounting (None when off/unpartitioned)."""
        if self.planner is None:
            return None
        return self.planner.cache_stats()

    def last_degraded(self) -> Optional[dict]:
        """Degraded-batch info from the most recent dispatch.

        ``None`` when every partition served the batch (or the engine is
        unpartitioned); else ``{"partitions": [...], "label_ranges":
        [(lo, hi), ...]}`` — see :attr:`ScatterGatherPlanner.last_degraded`.
        Callers must read this synchronously after the dispatch that
        produced it (the batcher snapshots it per in-flight batch).
        """
        if self.planner is None:
            return None
        return getattr(self.planner, "last_degraded", None)

    def measure_batch_seconds(self, batch: int, iters: int = 3,
                              tier: int = 0) -> float:
        """Median wall seconds for one ``batch``-sized dispatch (warmed).

        The drain-rate probe behind ``queue_depth="auto"``: sentinel (empty)
        queries traverse the same levels and sorts as real ones, so the
        figure bounds the device-side service time per bucket. With
        ``tier > 0`` the probe runs at that beam tier — the same
        measurement calibrates the :class:`~repro.serving.slo
        .BeamTierPolicy` cost model.
        """
        bucket = self.bucket_for(batch)
        d = self.tree.d
        xi = jnp.full((bucket, self.config.ell_width), d, jnp.int32)
        xv = jnp.zeros((bucket, self.config.ell_width), jnp.float32)
        jax.block_until_ready(self._run(xi, xv, tier=tier))  # warm bucket
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(self._run(xi, xv, tier=tier))
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    def latency_summary(self) -> dict:
        return self.stats.summary()
