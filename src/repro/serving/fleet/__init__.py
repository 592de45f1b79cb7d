"""Cross-process partition fleet: workers, launcher, supervision, RPC.

``PartitionFleet.launch(P).attach(engine)`` moves a partitioned engine's
per-level scatter-gather work into P worker processes — each with its own
JAX runtime and device memory — while the coordinator keeps the router head
and the tiny per-level beam merges. Results stay bitwise-identical to
in-process serving (pinned by tests/test_fleet_gateway.py).

Robustness lives here too: :class:`FleetSupervisor` respawns dead workers
(state machine UP → SUSPECT → RESTARTING → UP, or FAILED on budget
exhaustion), the fleet's ``degraded_policy`` decides whether a partition
loss fails queries or serves survivor-exact partial rankings, and
:class:`FaultInjector` is the deterministic chaos seam the test suite and
``bench_gateway --chaos`` drive failures through.
"""

from repro.serving.fleet.launcher import (
    ChipHeldError,
    PartitionFleet,
    WorkerHandle,
    launch_workers,
    partition_payload,
)
from repro.serving.fleet.rpc import (
    FaultInjector,
    FaultRule,
    RemoteError,
    WorkerConnection,
)
from repro.serving.fleet.supervisor import (
    STATE_FAILED,
    STATE_RESTARTING,
    STATE_SUSPECT,
    STATE_UP,
    WORKER_STATES,
    FleetSupervisor,
)

__all__ = [
    "ChipHeldError",
    "FaultInjector",
    "FaultRule",
    "FleetSupervisor",
    "PartitionFleet",
    "RemoteError",
    "STATE_FAILED",
    "STATE_RESTARTING",
    "STATE_SUSPECT",
    "STATE_UP",
    "WORKER_STATES",
    "WorkerConnection",
    "WorkerHandle",
    "launch_workers",
    "partition_payload",
]
