"""Scatter–gather query planner over a :class:`PartitionedIndex`.

Query path (``sync="level"``, **bitwise-exact**):

1. **route** — the replicated router head runs the ordinary jitted beam
   search over the levels above the split, producing the global beam.
2. **scatter** — the beam is broadcast to every partition; each partition
   scores *only the beam rows it owns* (out-of-range rows park on its
   phantom chunk) through :func:`repro.core.tree.owned_level_combined` — the
   same arithmetic the unpartitioned traversal uses, on sliced layers with
   identical ELL pad widths, so owned rows are bit-identical.
3. **gather + select** — the planner reassembles the global ``[n, b, B]``
   candidate tensor from the owners and applies the canonical
   (score desc, id asc) :func:`~repro.core.beam.beam_select`. Steps 2–3
   repeat per partitioned level; the final level's select *is* the global
   top-k — results are **bitwise-identical** to the unpartitioned tree for
   every MSCM method (pinned by tests and a structural benchmark flag).

``sync="pipelined"`` keeps the same bitwise contract while taking the
per-level exchange off the partitions' critical path. In ``"level"`` mode a
partition's level-(l+1) matmul cannot start until the coordinator has
gathered every partition's level-l candidates, selected, and scattered the
winning beam back — P devices idle behind one host-coordinated exchange
every level. The pipelined mode **double-buffers the exchange with
speculation**:

* each partition runs a *local* canonical select over the candidates it
  owns (:func:`_local_select` — same ``(score desc, id asc)`` order as the
  global select, via an id-presorted ``top_k``) and speculatively expands
  those survivors through the level-(l+1) MSCM **now**, through the same
  ``owned_level_combined`` continuation;
* canonical-order dominance guarantees every *globally* surviving
  candidate is present in its owner's local beam (the owner's competitor
  set is a subset of the global one, and unowned rows are junk-id-shifted
  past every real candidate so they lose all ties) — so the coordinator
  never needs the ``[n, b, B]`` candidate tensor at all: it **canonically
  merges the P local beams** (:func:`_merge_beams`, ``[n, w]`` ids +
  scores each) and that *is* the global select, bit for bit. Per-level
  communication drops ~B× and the coordinator's sort shrinks from ``b·B``
  wide to ``P·w``;
* reconciliation (:func:`_reconcile_select`, fused with the next local
  select) aligns the canonical winners with the speculative expansion — a
  cheap per-row gather that drops speculative losers and re-pins
  everything else to ``NEG_INF`` via the existing phantom machinery. No
  recompute, no second matmul: a partition's heavy matmul for level l+1
  depends on the merge of level **l−1**, not level l, so the exchange and
  the next level's compute genuinely overlap (JAX async dispatch realizes
  it as concurrent device streams). Results stay **bitwise-identical** to
  ``sync="level"`` (pinned by ``tests/test_pipelined.py`` across
  method × beam × qt × score_mode and the ``pipelined_parity`` flag).

Why per-level gathers at all: beam search prunes globally at every level. A
partition-local beam keeps candidates global pruning discarded, and their
descendants can out-rank reference results at the leaves — a single final
merge is a (weakly better, recall ≥) *different* ranking. That mode exists
too (``sync="final"``): each partition runs the whole jitted sub-tree
traversal from the router handoff (one merge, no per-level sync — the
low-communication production topology); its top-k scores dominate the exact
result's but are not bitwise-reproducible, so serving defaults to
``"level"``.

Communication is activations only — ``[n, b]`` beams out, ``[n, b, B]``
candidates back, per level — while the weights stay put: with a
:class:`~repro.index.placement.Placement` each partition lives on its own
device (column of the ``("data", "model")`` mesh), batches split over the
data axis, and partitions score concurrently (JAX dispatch is async; the
gather only synchronizes at the select).

With ``cache_entries > 0`` a :class:`~repro.index.cache.HotBeamCache` maps
router-beam signatures to the set of partitions that own any surviving row;
partitions owning nothing are skipped for the whole batch (bitwise-safe —
ownership is nested, so they could only ever contribute ``NEG_INF``). The
lookup materializes the router beam on the host (one small sync per batch),
which is why it is opt-in.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mscm as mscm_lib
from repro.core.beam import NEG_INF, beam_select, topk_canonical
from repro.core.tree import owned_level_combined
from repro.index.cache import HotBeamCache
from repro.index.partition import PartitionedIndex
from repro.index.placement import Placement


def reference_topk_width(
    n_cols: Sequence[int], branching: Sequence[int], beam: int, topk: int
) -> int:
    """Output width of the unpartitioned ``infer`` for these settings.

    Mirrors the traversal's clamps: ``next_b = min(beam-or-topk, n_cols)``
    further clamped by the candidate count ``b · B`` (jnp slicing clamps).
    """
    b = 1
    for li, ncol in enumerate(n_cols):
        want = topk if li == len(n_cols) - 1 else beam
        b = min(want, int(ncol), b * int(branching[li]))
    return b


_owned_level_scores = functools.partial(
    jax.jit,
    static_argnames=("branching", "d", "method", "score_mode", "qt"),
)(owned_level_combined)
"""Jitted :func:`repro.core.tree.owned_level_combined` — one partition's
owned slice of a level: ``([n, b, B] combined, owned)``. ``chunk_start`` /
``chunk_count`` are traced so equal-shape partitions share one
compilation."""


def _local_select(
    parent_ids: jax.Array,  # int32 [n, b] GLOBAL chunk ids at this level
    combined: jax.Array,    # f32 [n, b, B] this partition's owned candidates
    owned: jax.Array,       # bool [n, b]
    *,
    n_cols: int,            # valid columns at this level
    n_chunks: int,          # GLOBAL chunk count at this level (junk shift)
    next_b: int,
) -> Tuple[jax.Array, jax.Array]:
    """Partition-local canonical select — the speculation step.

    Identical ``(score desc, id asc)`` ordering to the coordinator's global
    select, over the partition's own candidate slice. Unowned beam rows are
    **id-shifted** onto the junk parent ``n_chunks`` (one past the last real
    chunk anywhere in the tree) so their ``NEG_INF`` children carry ids
    strictly greater than every real or padding candidate: they lose every
    tie, which is what makes the speculative set a guaranteed superset of
    the partition's globally-surviving candidates — even ones whose score
    is exactly ``NEG_INF``.

    Runs once per partition per level (vs the coordinator's one global
    select), so it uses a cheaper kernel than ``beam_select``'s full
    two-key sort: the beam is first ordered by parent id (an ``O(b)``-wide
    argsort), which makes the flattened candidate ids ascending in index —
    ``lax.top_k``'s lowest-index tie-break then *is* the canonical lowest-id
    tie-break, at a fraction of the sort's cost. Returns the same bits as
    ``beam_select`` in the same canonical order.
    """
    n, b = parent_ids.shape
    B = combined.shape[-1]
    shifted = jnp.where(owned, parent_ids, jnp.int32(n_chunks))
    order = jnp.argsort(shifted, axis=1)
    p_sorted = jnp.take_along_axis(shifted, order, axis=1)
    c_sorted = jnp.take_along_axis(combined, order[..., None], axis=1)
    child_ids = p_sorted[:, :, None] * B + jnp.arange(B)[None, None, :]
    valid = child_ids < n_cols
    scores = jnp.where(valid, c_sorted, NEG_INF).reshape(n, b * B)
    k = min(next_b, b * B)  # the reference width clamp (slicing semantics)
    top_scores, top_idx = jax.lax.top_k(scores, k)
    top_ids = jnp.take_along_axis(
        child_ids.reshape(n, b * B), top_idx, axis=1
    )
    return top_ids.astype(jnp.int32), top_scores


_spec_select = functools.partial(
    jax.jit, static_argnames=("n_cols", "n_chunks", "next_b")
)(_local_select)


def _reconcile(
    winner_ids: jax.Array,   # int32 [n, w] canonical global beam (level l-1)
    spec_ids: jax.Array,     # int32 [n, w] speculative local beam (level l-1)
    spec_combined: jax.Array,  # f32 [n, w, B] speculative level-l candidates
    chunk_start: jax.Array,  # scalar: partition's first chunk at level l
    chunk_count: jax.Array,  # scalar: partition's real chunks at level l
) -> Tuple[jax.Array, jax.Array]:
    """Align the speculative expansion with the canonical global beam.

    For each globally-selected parent, find it in the speculative beam (a
    per-row ``searchsorted`` through the id-sorted speculative ids) and
    gather its precomputed level-l candidate row. Winners owned by this
    partition are guaranteed present (see :func:`_local_select`); everything
    else — losers, rows owned elsewhere — re-pins to exactly ``NEG_INF``,
    the same bits :func:`~repro.core.tree.owned_level_combined` would have
    produced. Returns ``(combined [n, w, B], owned [n, w])`` in canonical
    beam order, indistinguishable from the non-speculative path.
    """
    owned = (winner_ids >= chunk_start) & (winner_ids < chunk_start + chunk_count)
    order = jnp.argsort(spec_ids, axis=1)
    sorted_ids = jnp.take_along_axis(spec_ids, order, axis=1)
    pos = jax.vmap(jnp.searchsorted)(sorted_ids, winner_ids)
    pos = jnp.clip(pos, 0, spec_ids.shape[1] - 1)
    hit = jnp.take_along_axis(sorted_ids, pos, axis=1) == winner_ids
    src = jnp.take_along_axis(order, pos, axis=1)
    combined = jnp.take_along_axis(spec_combined, src[..., None], axis=1)
    mask = owned & hit
    return jnp.where(mask[..., None], combined, NEG_INF), mask


@functools.partial(jax.jit, static_argnames=("n_cols", "n_chunks", "next_b"))
def _reconcile_select(
    winner_ids: jax.Array,     # int32 [n, w] canonical beam from the merge
    spec_ids: jax.Array,       # int32 [n, w] previous speculative beam
    spec_combined: jax.Array,  # f32 [n, w, B] speculative this-level scores
    chunk_start: jax.Array,
    chunk_count: jax.Array,
    *,
    n_cols: int,
    n_chunks: int,
    next_b: int,
) -> Tuple[jax.Array, jax.Array]:
    """Fused reconcile + local select: one cheap dispatch per level.

    Both steps are gathers/sorts over ``[n, w(, B)]`` tensors with the same
    operands, so fusing them keeps the partition's per-level exchange to a
    single small XLA program between the heavy speculative matmuls.
    """
    combined, owned = _reconcile(
        winner_ids, spec_ids, spec_combined, chunk_start, chunk_count
    )
    return _local_select(
        winner_ids, combined, owned,
        n_cols=n_cols, n_chunks=n_chunks, next_b=next_b,
    )


@functools.partial(jax.jit, static_argnames=("width",))
def _merge_beams(
    ids: Tuple[jax.Array, ...],     # per partition: int32 [n, w]
    scores: Tuple[jax.Array, ...],  # per partition: f32 [n, w]
    *,
    width: int,
) -> Tuple[jax.Array, jax.Array]:
    """Canonical merge of the partitions' speculative beams == global select.

    Every candidate that survives the *global* canonical select is present
    in its owner's speculative beam (:func:`_local_select` dominance), and
    canonical ``(score desc, id asc)`` order is a total order — so the
    top-``width`` of the concatenated local beams is exactly the
    top-``width`` of the full candidate set, at P·w merge cost instead of a
    b·B-wide sort, with only ``[n, w]`` beams ever crossing devices
    (``width`` carries the unpartitioned traversal's ``min(next_b, b·B)``
    clamp so degenerate narrow levels keep the reference output shape).
    Delegates the tie-break-critical sort to :func:`merge_topk` so the
    canonical ordering lives in exactly one place.
    """
    merged_scores, merged_ids = merge_topk(
        jnp.concatenate(scores, axis=1),
        jnp.concatenate(ids, axis=1),
        width=width,
    )
    return merged_ids, merged_scores


@functools.partial(jax.jit, static_argnames=("n_cols", "next_b"))
def _gather_select(
    parent_ids: jax.Array,
    parts_combined: Tuple[jax.Array, ...],
    parts_owned: Tuple[jax.Array, ...],
    *,
    n_cols: int,
    next_b: int,
) -> Tuple[jax.Array, jax.Array]:
    """Compose the owners' slices into the global candidate tensor + select.

    Every beam row is owned by at most one partition; rows owned by none
    (global phantoms) stay ``NEG_INF``, exactly what the canonical mask
    pins them to in the unpartitioned traversal.
    """
    acc = jnp.full_like(parts_combined[0], NEG_INF)
    for combined, owned in zip(parts_combined, parts_owned):
        acc = jnp.where(owned[..., None], combined, acc)
    return beam_select(parent_ids, acc, n_cols, next_b)


@functools.partial(jax.jit, static_argnames=("width",))
def merge_topk(
    scores: jax.Array, labels: jax.Array, *, width: int
) -> Tuple[jax.Array, jax.Array]:
    """Canonical (score desc, id asc) top-``width`` of concatenated
    per-partition candidates — the ``sync="final"`` merge, delegated to
    the one shared two-key sort in :func:`repro.core.beam.topk_canonical`."""
    ids, top_scores = topk_canonical(scores, labels, width)
    return top_scores, ids


_scatter_dense = jax.jit(mscm_lib.scatter_dense, static_argnums=2)

SYNC_MODES = ("level", "pipelined", "final")


class TransportDegraded(RuntimeError):
    """A partition was lost mid-exchange but the batch is retryable.

    Raised by a transport whose degraded policy is ``"serve_partial"``
    after it has removed the lost partition from its live set; the
    coordinator replays the batch from ``begin`` over the survivors (the
    workers' per-batch speculation state restarts cleanly at ``begin``).
    """

    def __init__(self, pid: int, cause: BaseException) -> None:
        super().__init__(f"partition {pid} lost mid-exchange: {cause}")
        self.pid = pid
        self.cause = cause


class BeamTransport:
    """Where the pipelined exchange's partition halves run.

    The per-level pipelined protocol has two sides: P partitions computing
    local canonical beams (score + speculate, the heavy half) and a
    coordinator merging P tiny ``[n, w]`` beams (:func:`_merge_beams`). A
    ``BeamTransport`` abstracts the partition side so the same coordinator
    loop (:meth:`ScatterGatherPlanner._infer_transport`) drives in-process
    partitions or remote worker processes — the fleet RPC implementation is
    :class:`repro.serving.fleet.PartitionFleet`.

    Protocol, per query batch:

    * :meth:`begin` — ship the batch (ELL ``idx``/``val``) and the router
      handoff beam; every partition computes its level-``li0`` local beam
      and speculatively expands level ``li0+1``. Returns the P local beams
      ``[(ids [n, w], scores [n, w]), ...]`` in partition order.
    * :meth:`step` — ship the canonical winners of level ``level - 1``;
      every partition reconciles its speculation, locally selects level
      ``level``, and speculates ``level + 1``. Returns the P local beams.

    All arrays cross the transport as host ``numpy`` — the tiny ``[n, w]``
    beams are the only per-level traffic, which is what makes the exchange
    bandwidth-trivial over a socket.
    """

    @property
    def n_partitions(self) -> int:
        raise NotImplementedError

    def begin(
        self,
        x_idx: np.ndarray,
        x_val: np.ndarray,
        parent_ids: np.ndarray,
        scores: np.ndarray,
        *,
        beam: Optional[int] = None,
        qt: Optional[int] = None,
    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """``beam``/``qt`` override the partitions' configured settings for
        this batch only (adaptive beam tiers; ``None`` = the configured
        full values — the coordinator omits them unless degraded, so tier-0
        traffic is byte-identical to a transport without tiers)."""
        raise NotImplementedError

    def step(
        self, level: int, winner_ids: np.ndarray
    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        raise NotImplementedError

    def down_partitions(self) -> List[int]:
        """Partitions excluded from the current batch (degraded mode).

        Default: none. A degraded-capable transport returns the pids whose
        beams were missing from the batch it just served, so the
        coordinator can stamp the result with the unsearched label ranges.
        """
        return []


class ScatterGatherPlanner:
    """Executes partitioned queries; see the module docstring for the path.

    With ``placement`` the partitions' layer tensors are copied onto their
    assigned mesh columns at construction and every scatter/gather hop is an
    explicit ``device_put`` (batch dim split over the column's data axis);
    without one, everything runs on the default device — same arithmetic,
    same results.
    """

    def __init__(
        self,
        index: PartitionedIndex,
        *,
        beam: int = 10,
        topk: int = 10,
        method: str = "mscm_dense",
        score_mode: str = "prod",
        qt: int = 8,
        sync: str = "level",
        placement: Optional[Placement] = None,
        cache_entries: int = 0,
        transport: Optional[BeamTransport] = None,
    ) -> None:
        if sync not in SYNC_MODES:
            raise ValueError(f"sync={sync!r}; choose from {SYNC_MODES}")
        self.transport = None
        if transport is not None:
            self._check_transport(sync, cache_entries, transport)
            self.transport = transport
        #: Degraded-batch info from the most recent :meth:`infer` over a
        #: transport: ``None`` when every partition participated, else
        #: ``{"partitions": [pid, ...], "label_ranges": [(lo, hi), ...]}``.
        self.last_degraded: Optional[dict] = None
        self.index = index
        self.beam = beam
        self.topk = topk
        self.method = method
        self.score_mode = score_mode
        self.qt = qt
        self.sync = sync
        self.placement = placement
        self.parts = index.parts
        if placement is not None:
            if len(placement.array_shardings) != index.n_partitions:
                raise ValueError(
                    f"placement covers {len(placement.array_shardings)} "
                    f"partitions, index has {index.n_partitions}"
                )
            self.parts = [
                p.device_put(sh)
                for p, sh in zip(index.parts, placement.array_shardings)
            ]
        self._needs_dense = method in (
            "mscm_dense", "mscm_pallas", "mscm_pallas_pregather",
        )
        # The router head is always exact f32 (only the partitions are
        # quantized — repro.quant.quantize_index), so a quantized method
        # routes through its exact grouped twin: same grouping, same
        # epilogue, f32 tiles.
        self._router_method = (
            "mscm_pallas_grouped"
            if method == "mscm_pallas_grouped_q" else method
        )
        self.cache: Optional[HotBeamCache] = None
        if cache_entries:
            if sync == "final":
                # The final-merge path always traverses every partition
                # (dropping one changes the merged candidate panel), so a
                # cache would be built but never consulted — refuse rather
                # than silently no-op.
                raise ValueError(
                    'cache_entries is only meaningful for the exact sync '
                    'modes ("level"/"pipelined"), not sync="final"'
                )
            bounds = [p.chunk_start for p in index.manifest.partitions]
            bounds.append(index.manifest.partitions[-1].chunk_end)
            self.cache = HotBeamCache(cache_entries, bounds)

    # -- transport (cross-process partitions) -------------------------------
    def _check_transport(
        self, sync: str, cache_entries: int, transport: BeamTransport
    ) -> None:
        if sync != "pipelined":
            raise ValueError(
                'a BeamTransport requires sync="pipelined" (the only mode '
                "whose per-level exchange is the tiny local-beam protocol); "
                f"got sync={sync!r}"
            )
        if cache_entries:
            raise ValueError(
                "beam_cache is incompatible with a BeamTransport: the "
                "hot-beam owner-set skip is a host-side optimization of the "
                "in-process scatter, and remote workers always participate"
            )

    def set_transport(self, transport: Optional[BeamTransport]) -> None:
        """Route the partition halves through ``transport`` (None = local).

        The coordinator keeps the router head and the per-level merge; the
        partitions' score/speculate halves run wherever the transport says
        (e.g. the fleet's worker processes). Results stay bitwise-identical
        to in-process serving: both sides run the same jitted programs on
        the same partition slices, and :func:`_merge_beams` is
        concatenation-order independent.
        """
        if transport is not None:
            self._check_transport(
                self.sync, 0 if self.cache is None else 1, transport
            )
            if transport.n_partitions != self.index.n_partitions:
                raise ValueError(
                    f"transport serves {transport.n_partitions} partitions, "
                    f"index has {self.index.n_partitions}"
                )
        self.transport = transport

    def _infer_transport(self, x_idx, x_val, parent_ids, scores, *,
                         beam: int, qt: int):
        """Coordinator half of the pipelined exchange over a transport.

        If the transport loses a partition mid-exchange and its policy
        allows partial service, it raises :class:`TransportDegraded` after
        shrinking its live set; the whole batch is replayed over the
        survivors. The loop is bounded: every replay follows the permanent
        loss of at least one partition. Degraded merges stay bitwise-exact
        for surviving-partition labels: each survivor's local beam is
        already merge-width wide (``k = min(next_b, b·B)`` equals the
        coordinator's width recurrence), and a path's score is a
        deterministic chain independent of which other candidates shared
        the beam — dropping a partition only frees panel slots, it cannot
        perturb any survivor's bits.
        """
        while True:
            try:
                w_scores, w_ids = self._transport_exchange(
                    x_idx, x_val, parent_ids, scores, beam=beam, qt=qt
                )
                break
            except TransportDegraded:
                continue  # replay over the survivors
        down = sorted(self.transport.down_partitions())
        if down:
            infos = self.index.manifest.partitions
            self.last_degraded = {
                "partitions": down,
                "label_ranges": [
                    (int(infos[p].label_start), int(infos[p].label_end))
                    for p in down
                ],
            }
        return w_scores, w_ids

    def _transport_exchange(self, x_idx, x_val, parent_ids, scores, *,
                            beam: int, qt: int):
        """One full begin/step/merge pass over the transport.

        Same width/level recurrence as :meth:`_infer_pipelined`; the
        partitions' reconcile/select/speculate halves run behind
        ``self.transport`` (each worker mirrors the in-process device-stream
        schedule, so the speculative matmuls still overlap this merge loop).
        """
        idx = self.index
        depth = len(idx.n_cols)
        width = parent_ids.shape[1]  # router handoff beam width
        # Tier overrides ride the begin header only when they actually
        # differ from the workers' loaded settings — full-beam batches stay
        # byte-identical on the wire to a fleet that predates tiers.
        overrides = {}
        if beam != self.beam:
            overrides["beam"] = beam
        if qt != self.qt:
            overrides["qt"] = qt
        beams = self.transport.begin(
            np.asarray(x_idx), np.asarray(x_val),
            np.asarray(parent_ids), np.asarray(scores),
            **overrides,
        )
        w_ids = w_scores = None
        for li in range(idx.level, depth):
            is_last = li == depth - 1
            next_b = min(self.topk if is_last else beam, idx.n_cols[li])
            width = min(next_b, width * idx.branching[li])
            if li > idx.level:
                beams = self.transport.step(li, np.asarray(w_ids))
            w_ids, w_scores = _merge_beams(
                tuple(jnp.asarray(i) for i, _ in beams),
                tuple(jnp.asarray(s) for _, s in beams),
                width=width,
            )
        return w_scores, w_ids

    # -- device hops --------------------------------------------------------
    def _to_partition(self, pid: int, *arrays):
        if self.placement is None:
            return arrays
        sh = self.placement.batch_shardings[pid]
        return tuple(jax.device_put(a, sh) for a in arrays)

    def _to_coordinator(self, *arrays):
        if self.placement is None:
            return arrays
        dev = self.placement.coordinator
        return tuple(jax.device_put(a, dev) for a in arrays)

    # -- query path ---------------------------------------------------------
    def _route(self, x_idx: jax.Array, x_val: jax.Array, *,
               beam: int, qt: int):
        """Router head: the global beam after the levels above the split."""
        return self.index.head.infer(
            x_idx, x_val, beam=beam, topk=beam,
            method=self._router_method, score_mode=self.score_mode,
            qt=qt,
        )

    def _active_partitions(self, parent_ids: jax.Array) -> List[int]:
        """Partitions participating in this batch.

        Without a cache: all of them, no host sync. With one: the cached
        owner set of each row's router-beam signature — partitions owning
        no surviving row are skipped for every level (ownership is nested),
        which cannot change any bit of the gather (their slices are all
        ``NEG_INF`` by construction).
        """
        if self.cache is None:
            return list(range(self.index.n_partitions))
        return self.cache.active_partitions(np.asarray(parent_ids))

    def _partition_inputs(self, x_idx, x_val, active: Sequence[int]):
        """Per-partition (xi, xv, x_dense) resident on the partition's devices.

        The dense [n, d+1] query table is the expensive piece (d can be
        millions); partitions sharing a batch sharding — all of them when no
        placement is set, column-mates under LPT packing — share one copy.
        """
        out: Dict[int, tuple] = {}
        by_sharding: Dict = {}
        for pid in active:
            key = (
                self.placement.batch_shardings[pid]
                if self.placement is not None else None
            )
            if key not in by_sharding:
                xi_p, xv_p = self._to_partition(pid, x_idx, x_val)
                xd_p = (
                    _scatter_dense(xi_p, xv_p, self.index.d)
                    if self._needs_dense else None
                )
                by_sharding[key] = (xi_p, xv_p, xd_p)
            out[pid] = by_sharding[key]
        return out

    def infer(
        self, x_idx: jax.Array, x_val: jax.Array, *,
        beam: Optional[int] = None, qt: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Global (scores [n, k], labels [n, k]) for a query batch.

        ``beam``/``qt`` override the configured settings for this call only
        (the adaptive tier path — the coordinator picks a tier per batch);
        ``None`` keeps the constructor values, and that default path is
        unchanged down to the wire. Every sync mode clamps widths from the
        effective beam, so partition-local selects stay bitwise-exact *at
        that tier*.
        """
        beam = self.beam if beam is None else int(beam)
        qt = self.qt if qt is None else int(qt)
        self.last_degraded = None
        scores, parent_ids = self._route(x_idx, x_val, beam=beam, qt=qt)
        if self.transport is not None:
            return self._infer_transport(
                x_idx, x_val, parent_ids, scores, beam=beam, qt=qt
            )
        if self.sync == "final":
            return self._infer_final(
                x_idx, x_val, parent_ids, scores, beam=beam, qt=qt
            )
        active = self._active_partitions(parent_ids)
        run = (
            self._infer_pipelined if self.sync == "pipelined"
            else self._infer_level
        )
        return run(x_idx, x_val, parent_ids, scores, active, beam=beam, qt=qt)

    def _level_owned(self, li, pid, inputs, parent_ids, scores, span,
                     qt: Optional[int] = None):
        """One partition's owned candidate slice of level ``li`` (jitted)."""
        idx = self.index
        part, info = self.parts[pid], idx.manifest.partitions[pid]
        lay = part.layers[li - idx.level]
        c_real = lay.chunk_rows.shape[0] - 1  # minus phantom pad
        xi_p, xv_p, xd_p = inputs[pid]
        return _owned_level_scores(
            lay, idx.branching[li], idx.d, xi_p, xv_p, xd_p,
            parent_ids, scores,
            jnp.int32(info.chunk_start * span), jnp.int32(c_real),
            method=self.method, score_mode=self.score_mode,
            qt=self.qt if qt is None else qt,
        )

    def _infer_level(self, x_idx, x_val, parent_ids, scores, active, *,
                     beam: int, qt: int):
        idx = self.index
        inputs = self._partition_inputs(x_idx, x_val, active)
        depth = len(idx.n_cols)
        for li in range(idx.level, depth):
            is_last = li == depth - 1
            next_b = min(
                self.topk if is_last else beam, idx.n_cols[li]
            )
            combined, owned = [], []
            # Chunk ranges at this level: the split ranges scaled by the
            # branching products of the levels in between (tree order).
            span = int(np.prod(idx.branching[idx.level:li], dtype=np.int64)) \
                if li > idx.level else 1
            for pid in active:
                ids_p, sc_p = self._to_partition(pid, parent_ids, scores)
                comb_p, own_p = self._level_owned(
                    li, pid, inputs, ids_p, sc_p, span, qt=qt
                )
                comb_p, own_p = self._to_coordinator(comb_p, own_p)
                combined.append(comb_p)
                owned.append(own_p)
            parent_ids, scores = _gather_select(
                parent_ids, tuple(combined), tuple(owned),
                n_cols=idx.n_cols[li], next_b=next_b,
            )
        return scores, parent_ids

    def _infer_pipelined(self, x_idx, x_val, parent_ids, scores, active, *,
                         beam: int, qt: int):
        """Double-buffered exchange: level-l select ∥ level-(l+1) matmul.

        Each iteration, per partition and in device-stream order:

        1. reconcile the previous level's winners against the speculative
           expansion and run the *local* canonical select (one fused cheap
           dispatch, :func:`_reconcile_select`) — at the first partitioned
           level, score the scattered router handoff instead;
        2. ship the tiny ``[n, w]`` speculative beam to the coordinator —
           *before* any heavy work, so the merge is never queued behind the
           matmul it is meant to overlap;
        3. speculatively expand the local survivors through the next
           level's MSCM (the heavy matmul — depends only on partition-local
           data, so it runs concurrently with the coordinator's merge);

        then on the coordinator: 4. canonically merge the local beams
        (:func:`_merge_beams` — bitwise the global select, because every
        global winner is in its owner's local beam) and scatter the winner
        ids (ids only — ``[n, w]`` int32) back to the partitions for the
        next iteration's reconcile. All dispatch is async — the host never
        blocks, and a partition's level-(l+1) matmul transitively depends
        on the *level-(l-1)* merge, not the level-l one: one full level of
        slack for the exchange to hide in.

        Versus ``sync="level"``, per-level communication drops from the
        full ``[n, b, B]`` candidate tensor + ownership mask per partition
        to two ``[n, w]`` beams, and the coordinator's sort shrinks from
        ``b·B`` wide to ``P·w``.
        """
        idx = self.index
        infos = idx.manifest.partitions
        inputs = self._partition_inputs(x_idx, x_val, active)
        depth = len(idx.n_cols)
        li0 = idx.level
        beam_p: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        spec_comb: Dict[int, jax.Array] = {}
        spec_ids: Dict[int, jax.Array] = {}
        w_ids = parent_ids
        width = parent_ids.shape[1]  # router handoff beam width
        span = span_next = 1
        for li in range(li0, depth):
            is_last = li == depth - 1
            next_b = min(self.topk if is_last else beam, idx.n_cols[li])
            width = min(next_b, width * idx.branching[li])
            # (1) local canonical beams for level li.
            if li == li0:
                for pid in active:  # scored from the router handoff
                    ids, sc = self._to_partition(pid, parent_ids, scores)
                    comb, own = self._level_owned(
                        li0, pid, inputs, ids, sc, 1, qt=qt
                    )
                    beam_p[pid] = _spec_select(
                        ids, comb, own,
                        n_cols=idx.n_cols[li], n_chunks=idx.n_cols[li - 1],
                        next_b=next_b,
                    )
            else:
                for pid in active:
                    info = infos[pid]
                    lay = self.parts[pid].layers[li - li0]
                    (ids,) = self._to_partition(pid, w_ids)
                    beam_p[pid] = _reconcile_select(
                        ids, spec_ids[pid], spec_comb[pid],
                        jnp.int32(info.chunk_start * span),
                        jnp.int32(lay.chunk_rows.shape[0] - 1),
                        n_cols=idx.n_cols[li], n_chunks=idx.n_cols[li - 1],
                        next_b=next_b,
                    )
            # (2) beam transfers to the coordinator go ahead of the matmul.
            gathered = [
                self._to_coordinator(*beam_p[pid]) for pid in active
            ]
            # (3) canonical merge == the global select for level li —
            # dispatched BEFORE the expansions so that when the coordinator
            # shares a device with a partition, the merge is not queued
            # behind that partition's matmul (it depends only on the tiny
            # beams transferred above).
            w_ids, w_scores = _merge_beams(
                tuple(i for i, _ in gathered),
                tuple(s for _, s in gathered),
                width=width,
            )
            # (4) speculative expansion of level li+1 — the double buffer.
            if not is_last:
                span_next = span * idx.branching[li]
                for pid in active:
                    s_ids, s_sc = beam_p[pid]
                    spec_comb[pid], _ = self._level_owned(
                        li + 1, pid, inputs, s_ids, s_sc, span_next, qt=qt
                    )
                    spec_ids[pid] = s_ids
            span = span_next
        return w_scores, w_ids

    def _run_partition(self, part, info, ids_p, sc_p, xi_p, xv_p,
                       beam: Optional[int] = None, qt: Optional[int] = None):
        """One partition's whole-sub-tree traversal from the router beam.

        Localizes the global beam (out-of-range rows -> phantom chunk,
        score ``NEG_INF``) and runs the jitted continuation — shared by the
        ``"final"`` merge path and :meth:`profile` so the measured traversal
        can never drift from the served one.
        """
        c_real = info.chunk_end - info.chunk_start
        owned = (ids_p >= info.chunk_start) & (ids_p < info.chunk_end)
        local_ids = jnp.where(owned, ids_p - info.chunk_start, c_real)
        local_sc = jnp.where(owned, sc_p, NEG_INF)
        return part.infer(
            xi_p, xv_p,
            beam=self.beam if beam is None else beam, topk=self.topk,
            method=self.method, score_mode=self.score_mode,
            qt=self.qt if qt is None else qt,
            init_parent_ids=local_ids.astype(jnp.int32),
            init_scores=local_sc, clamp_chunks=True,
        )

    def _infer_final(self, x_idx, x_val, parent_ids, scores, *,
                     beam: int, qt: int):
        """Single-merge mode: whole sub-tree traversals, one canonical merge.

        Not bitwise-reproducible against the unpartitioned tree — each
        partition prunes locally, so the merged top-k *dominates* the exact
        result (every merged score >= its exact counterpart, recall >=).
        """
        idx = self.index
        inputs = self._partition_inputs(
            x_idx, x_val, range(idx.n_partitions)
        )
        width = reference_topk_width(
            idx.n_cols, idx.branching, beam, self.topk
        )
        out_s, out_l = [], []
        for pid, (part, info) in enumerate(
            zip(self.parts, idx.manifest.partitions)
        ):
            ids_p, sc_p = self._to_partition(pid, parent_ids, scores)
            xi_p, xv_p, _ = inputs[pid]
            s, l = self._run_partition(
                part, info, ids_p, sc_p, xi_p, xv_p, beam=beam, qt=qt
            )
            # Globalize: real leaves get the partition's label offset; local
            # phantoms (id >= the partition's label count) are pushed past
            # every real global id so they can never tie-break into the merge.
            gl = jnp.where(
                l < part.n_labels,
                l + info.label_start,
                idx.n_labels + info.label_start + l,
            )
            s, gl = self._to_coordinator(s, gl)
            out_s.append(s)
            out_l.append(gl)
        s_cat = jnp.concatenate(out_s, axis=1)
        l_cat = jnp.concatenate(out_l, axis=1)
        if s_cat.shape[1] < width:  # degenerate config; cannot fill the panel
            raise ValueError(
                f"merged candidate width {s_cat.shape[1]} < reference width "
                f"{width}; raise beam/topk or lower partitions"
            )
        return merge_topk(s_cat, l_cat, width=width)

    # -- diagnostics --------------------------------------------------------
    def cache_stats(self) -> Optional[dict]:
        """Hot-beam cache accounting, or None when the cache is off."""
        return self.cache.stats() if self.cache is not None else None

    def profile(
        self, x_idx: jax.Array, x_val: jax.Array
    ) -> List[float]:
        """Blocking per-partition sub-tree latency (ms) for one batch.

        Runs each partition's whole-sub-tree traversal (the ``"final"``
        path) serially with a blocking gather — the per-partition latency
        panel for benchmarks and capacity planning.
        """
        scores, parent_ids = jax.block_until_ready(
            self._route(x_idx, x_val, beam=self.beam, qt=self.qt)
        )
        out = []
        for pid, (part, info) in enumerate(
            zip(self.parts, self.index.manifest.partitions)
        ):
            ids_p, sc_p = self._to_partition(pid, parent_ids, scores)
            xi_p, xv_p = self._to_partition(pid, x_idx, x_val)
            t0 = time.perf_counter()
            jax.block_until_ready(
                self._run_partition(part, info, ids_p, sc_p, xi_p, xv_p)
            )
            out.append(1e3 * (time.perf_counter() - t0))
        return out

    def hit_counts(self, labels: np.ndarray) -> np.ndarray:
        """Per-partition share of a result set (occupancy accounting)."""
        return self.index.hit_counts(labels)
