"""v1 serving configuration: nested groups + a flat-kwarg back-compat shim.

``ServeConfig`` had grown 15 flat knobs across four concerns. The v1 surface
groups them by who consumes them:

* inference knobs stay top-level on :class:`ServeConfig` (``beam``,
  ``topk``, ``method``, ``ell_width``, ``max_batch``, ``score_mode``,
  ``qt``, ``shards``) — the engine reads these on every dispatch;
* :class:`AdmissionConfig` — the overload policy the :class:`~repro.serving
  .batcher.MicroBatcher` applies at the queue boundary;
* :class:`PartitionConfig` — the label-partitioned dispatch topology
  (:mod:`repro.index`);
* :class:`FleetConfig` — cross-process fleet resilience knobs;
* :class:`QuantConfig` — the compressed-weight storage tier
  (:mod:`repro.quant`): ``tier="exact"`` serves the f32 tree unchanged,
  the other tiers quantize the (partitioned) weights at engine build;
* :class:`SLOConfig` — latency-SLO adaptive inference: a ladder of
  degraded beam tiers the batcher may pick per dispatched batch when the
  queue backs up (:mod:`repro.serving.slo`). Off by default
  (``target_p99_ms=None``): every batch serves the full configured beam.

Back compat: the pre-v1 flat kwargs (``queue_depth=``, ``partitions=``, …)
still work — ``ServeConfig`` routes them into the right nested group and
emits a :class:`DeprecationWarning` — and the read side keeps flat
*properties* (``config.partitions`` forwards to
``config.partition.partitions``) so existing call sites and benches keep
working unchanged. New code should write the nested form::

    ServeConfig(
        max_batch=64,
        partition=PartitionConfig(partitions=2, partition_sync="pipelined"),
        admission=AdmissionConfig(queue_depth="auto", deadline_ms=50.0),
    )
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional, Tuple, Union


@dataclasses.dataclass
class AdmissionConfig:
    """Overload policy consumed by the :class:`MicroBatcher` front end."""

    queue_depth: Union[int, str, None] = None  # bound | "auto" | unbounded
    shed_policy: str = "reject"                # "reject" | "shed-oldest"
    deadline_ms: Optional[float] = None        # default per-request deadline


@dataclasses.dataclass
class PartitionConfig:
    """Label-partitioned dispatch topology (:mod:`repro.index`)."""

    partitions: int = 1                    # label-space partitions
    partition_level: Optional[int] = None  # split level (None = auto)
    # "level"     — per-level exchange, bitwise-exact
    # "pipelined" — exchange overlapped with the next level's MSCM via
    #               speculative expansion; still bitwise-exact (and the only
    #               mode the cross-process fleet transport supports)
    # "final"     — one merge, no per-level sync; dominates, not bitwise
    partition_sync: str = "level"
    beam_cache: int = 0                    # hot-beam LRU entries (0 = off)


#: Valid :attr:`FleetConfig.degraded_policy` values.
DEGRADED_POLICIES = ("serve_partial", "reject")


@dataclasses.dataclass
class FleetConfig:
    """Cross-process fleet resilience: degraded serving + supervision.

    ``degraded_policy`` decides what a partition loss mid-query means:

    * ``"serve_partial"`` (default) — complete the beam exchange over the
      surviving partitions and stamp the result ``degraded`` with the
      unsearched label ranges; survivor scores stay bitwise-exact.
    * ``"reject"`` — fail the query with a typed ``worker_unavailable``
      (the pre-supervision behavior).

    The remaining knobs tune :class:`~repro.serving.fleet.FleetSupervisor`:
    how often it sweeps the fleet, how long one liveness probe may take,
    how many consecutive failed probes turn ``SUSPECT`` into a restart, and
    the exponential backoff / attempt budget of the respawn loop.
    """

    degraded_policy: str = "serve_partial"
    poll_interval_s: float = 0.5   # supervisor sweep cadence
    ping_timeout_s: float = 2.0    # per-worker probe bound
    suspect_after: int = 2         # failed probes before a restart
    backoff_base_s: float = 0.25   # delay after the first failed respawn
    backoff_max_s: float = 10.0    # backoff doubles up to this cap
    restart_budget: int = 5        # respawn attempts before FAILED

    def __post_init__(self) -> None:
        if self.degraded_policy not in DEGRADED_POLICIES:
            raise ValueError(
                f"degraded_policy={self.degraded_policy!r}; choose from "
                f"{DEGRADED_POLICIES}"
            )


#: Valid :attr:`QuantConfig.tier` values.
QUANT_TIERS = ("exact", "int8", "int8_pruned", "fp8")


@dataclasses.dataclass
class QuantConfig:
    """Compressed-weight storage tier (:mod:`repro.quant`).

    ``tier``:

    * ``"exact"`` (default) — f32 weights, bitwise-identical serving; the
      engine behaves exactly as before this config existed.
    * ``"int8"`` — per-(chunk, column) symmetric int8 weights + f32 scales,
      served through ``method="mscm_pallas_grouped_q"`` (dequantize
      in-register). ~4× smaller partitions; accuracy is a *measured
      contract* (recall@k floor / score-MAE bound, ``benchmarks/
      bench_quant.py``), not a bitwise claim.
    * ``"int8_pruned"`` — int8 plus a magnitude-pruned ELL re-pack keeping
      the top ``prune_keep`` fraction of each chunk's rows (pad width R
      shrinks too).
    * ``"fp8"`` — fp8-e4m3 storage (in-process serving only; the fleet
      wire is int8/f32).
    """

    tier: str = "exact"
    prune_keep: float = 0.5  # row fraction kept by the pruned re-pack

    def __post_init__(self) -> None:
        if self.tier not in QUANT_TIERS:
            raise ValueError(
                f"tier={self.tier!r}; choose from {QUANT_TIERS}"
            )
        if not 0.0 < self.prune_keep <= 1.0:
            raise ValueError(
                f"prune_keep must be in (0, 1]; got {self.prune_keep}"
            )


@dataclasses.dataclass
class SLOConfig:
    """Latency-SLO adaptive inference (:mod:`repro.serving.slo`).

    ``target_p99_ms=None`` (default) disables adaptive tiering: the engine
    exposes a single tier — the configured full ``(beam, qt)`` — and the
    batcher never degrades, so serving stays bitwise-identical to a config
    without this group. With a target set, the batcher picks a per-batch
    beam tier from queue depth and the batch's remaining deadline budget:
    tier 0 is always the full beam; deeper tiers trade recall for drain
    rate instead of shedding whole queries.

    ``tiers`` pins the degraded ladder explicitly as ``(beam, qt)`` pairs
    with strictly descending beams, all narrower than the configured full
    beam. Empty (default) auto-derives a halving ladder ``beam//2,
    beam//4, …`` down to ``min_beam`` at the configured ``qt``. Every tier
    must preserve the full-beam output panel width (the engine validates
    against the tree geometry at build) so a degraded result is narrower
    in *search*, never in *shape*.
    """

    target_p99_ms: Optional[float] = None  # None = adaptive tiering off
    tiers: Tuple[Tuple[int, int], ...] = ()  # explicit (beam, qt) ladder
    min_beam: int = 1                      # auto-ladder floor

    def __post_init__(self) -> None:
        if self.target_p99_ms is not None and self.target_p99_ms <= 0:
            raise ValueError(
                f"target_p99_ms must be positive; got {self.target_p99_ms}"
            )
        if self.min_beam < 1:
            raise ValueError(f"min_beam must be >= 1; got {self.min_beam}")
        prev = None
        for pair in self.tiers:
            if len(tuple(pair)) != 2:
                raise ValueError(
                    f"tiers entries are (beam, qt) pairs; got {pair!r}"
                )
            b, q = int(pair[0]), int(pair[1])
            if b < 1 or q < 1:
                raise ValueError(
                    f"tier (beam={b}, qt={q}) must be positive"
                )
            if prev is not None and b >= prev:
                raise ValueError(
                    f"tier beams must be strictly descending; got "
                    f"{[int(p[0]) for p in self.tiers]}"
                )
            prev = b


_ADMISSION_FIELDS = frozenset(
    f.name for f in dataclasses.fields(AdmissionConfig)
)
_PARTITION_FIELDS = frozenset(
    f.name for f in dataclasses.fields(PartitionConfig)
)
_FLEET_FIELDS = frozenset(
    f.name for f in dataclasses.fields(FleetConfig)
)
_QUANT_FIELDS = frozenset(
    f.name for f in dataclasses.fields(QuantConfig)
)
_SLO_FIELDS = frozenset(
    f.name for f in dataclasses.fields(SLOConfig)
)


@dataclasses.dataclass(init=False)
class ServeConfig:
    """Engine + serving-tier configuration (see the module docstring)."""

    beam: int = 10
    topk: int = 10
    method: str = "auto"          # "auto" resolves per backend (see engine)
    ell_width: int = 256          # query nnz cap (pad/truncate)
    max_batch: int = 256
    score_mode: str = "prod"
    qt: int = 8                   # grouped-kernel query-tile height
    shards: int = 1               # data-parallel device replicas per dispatch
    admission: AdmissionConfig = dataclasses.field(
        default_factory=AdmissionConfig
    )
    partition: PartitionConfig = dataclasses.field(
        default_factory=PartitionConfig
    )
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)

    def __init__(
        self,
        beam: int = 10,
        topk: int = 10,
        method: str = "auto",
        ell_width: int = 256,
        max_batch: int = 256,
        score_mode: str = "prod",
        qt: int = 8,
        shards: int = 1,
        admission: AdmissionConfig | None = None,
        partition: PartitionConfig | None = None,
        fleet: FleetConfig | None = None,
        quant: QuantConfig | None = None,
        slo: SLOConfig | None = None,
        **flat: Any,
    ) -> None:
        self.beam = beam
        self.topk = topk
        self.method = method
        self.ell_width = ell_width
        self.max_batch = max_batch
        self.score_mode = score_mode
        self.qt = qt
        self.shards = shards
        self.admission = admission if admission is not None else AdmissionConfig()
        self.partition = partition if partition is not None else PartitionConfig()
        self.fleet = fleet if fleet is not None else FleetConfig()
        self.quant = quant if quant is not None else QuantConfig()
        self.slo = slo if slo is not None else SLOConfig()
        if flat:
            adm = {k: v for k, v in flat.items() if k in _ADMISSION_FIELDS}
            prt = {k: v for k, v in flat.items() if k in _PARTITION_FIELDS}
            flt = {k: v for k, v in flat.items() if k in _FLEET_FIELDS}
            qnt = {k: v for k, v in flat.items() if k in _QUANT_FIELDS}
            slk = {k: v for k, v in flat.items() if k in _SLO_FIELDS}
            unknown = (
                set(flat) - set(adm) - set(prt) - set(flt) - set(qnt)
                - set(slk)
            )
            if unknown:
                raise TypeError(
                    f"ServeConfig got unexpected keyword argument(s) "
                    f"{sorted(unknown)}"
                )
            warnings.warn(
                f"flat ServeConfig kwarg(s) "
                f"{sorted(adm) + sorted(prt) + sorted(flt) + sorted(qnt) + sorted(slk)} "
                "are deprecated; pass admission=AdmissionConfig(...) / "
                "partition=PartitionConfig(...) / fleet=FleetConfig(...) / "
                "quant=QuantConfig(...) / slo=SLOConfig(...) instead",
                DeprecationWarning,
                stacklevel=2,
            )
            # replace(), not setattr: never mutate a caller-shared group.
            if adm:
                self.admission = dataclasses.replace(self.admission, **adm)
            if prt:
                self.partition = dataclasses.replace(self.partition, **prt)
            if flt:
                self.fleet = dataclasses.replace(self.fleet, **flt)
            if qnt:
                self.quant = dataclasses.replace(self.quant, **qnt)
            if slk:
                self.slo = dataclasses.replace(self.slo, **slk)

    # -- flat read-side forwarding (pre-v1 call sites) ----------------------
    @property
    def queue_depth(self) -> Union[int, str, None]:
        return self.admission.queue_depth

    @property
    def shed_policy(self) -> str:
        return self.admission.shed_policy

    @property
    def deadline_ms(self) -> Optional[float]:
        return self.admission.deadline_ms

    @property
    def partitions(self) -> int:
        return self.partition.partitions

    @property
    def partition_level(self) -> Optional[int]:
        return self.partition.partition_level

    @property
    def partition_sync(self) -> str:
        return self.partition.partition_sync

    @property
    def beam_cache(self) -> int:
        return self.partition.beam_cache

    @property
    def degraded_policy(self) -> str:
        return self.fleet.degraded_policy

    @property
    def tier(self) -> str:
        return self.quant.tier

    @property
    def prune_keep(self) -> float:
        return self.quant.prune_keep

    @property
    def target_p99_ms(self) -> Optional[float]:
        return self.slo.target_p99_ms
