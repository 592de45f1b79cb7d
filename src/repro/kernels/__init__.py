from repro.kernels import ops, ref
from repro.kernels.mscm_kernel import group_blocks_by_chunk, mscm_grouped
from repro.kernels.ops import (
    group_blocks_device,
    grouped_tile_bound,
    mscm_grouped_level,
    mscm_pallas_grouped,
)

__all__ = [
    "ops",
    "ref",
    "mscm_grouped",
    "mscm_grouped_level",
    "mscm_pallas_grouped",
    "group_blocks_by_chunk",
    "group_blocks_device",
    "grouped_tile_bound",
]
