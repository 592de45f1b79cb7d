"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and Mosaic refuses here what it would refuse on the chip (block
tiling, VMEM limits). Widths are amazon-670k's (paper Table 5) at branching
32: R = 496 rows per chunk, B = 32 columns, a 64-query bucket at beam 10,
query tiles of 8, queries in ELL rows of 256; the grouped levels also
compile at wiki-500k's feature dimension, which no dense table reaches. Every call passes ``interpret=False`` explicitly, since
the backend here is the CPU.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and the tests run under several workers.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.mscm_kernel import mscm_pregather
from repro.quant.kernels import mscm_grouped_q_level

D = 135_909          # amazon-670k feature dimension
D_WIKI = 2_381_304   # wiki-500k feature dimension
Q = 256              # ELL width of a query (ServeConfig.ell_width)
C, R, B = 1024, 496, 32
N, BEAM, QT = 64, 10, 8
A = N * BEAM         # active (query, parent) blocks per level


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name=None):
    """A Mosaic kernel is in the program; its HLO instruction carries
    ``name`` (the ``pallas_call``'s) where one is given."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        assert re.search(
            rf"%{name}(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
            text,
        )


def _compile_grouped(sharding, d):
    def level(xi, xv, rows, vals, bq, bc, ps):
        return ops.mscm_grouped_level(
            xi, xv, d, rows, vals, bq, bc, ps, qt=QT, mode="prod",
            interpret=False,
        )

    s = lambda shape, dt: _spec(sharding, shape, dt)  # noqa: E731
    return jax.jit(level).lower(
        s((N, Q), jnp.int32), s((N, Q), jnp.float32), s((C, R), jnp.int32),
        s((C, R, B), jnp.float32), s((A,), jnp.int32), s((A,), jnp.int32),
        s((A,), jnp.float32),
    ).compile()


def _compile_grouped_int8(sharding, d):
    def level(xi, xv, rows, vals, scales, bq, bc, ps):
        return mscm_grouped_q_level(
            xi, xv, d, rows, vals, scales, bq, bc, ps, qt=QT, mode="prod",
            interpret=False,
        )

    s = lambda shape, dt: _spec(sharding, shape, dt)  # noqa: E731
    return jax.jit(level).lower(
        s((N, Q), jnp.int32), s((N, Q), jnp.float32), s((C, R), jnp.int32),
        s((C, R, B), jnp.int8), s((C, B), jnp.float32), s((A,), jnp.int32),
        s((A,), jnp.int32), s((A,), jnp.float32),
    ).compile()


def test_grouped_compiles_for_v5e(one_chip):
    _assert_kernel(_compile_grouped(one_chip, D), "mscm_grouped")


def test_grouped_int8_compiles_for_v5e(one_chip):
    _assert_kernel(_compile_grouped_int8(one_chip, D), "mscm_grouped_q")


@pytest.mark.parametrize(
    "build, kernel",
    [(_compile_grouped, "mscm_grouped"),
     (_compile_grouped_int8, "mscm_grouped_q")],
    ids=["exact", "int8"],
)
def test_grouped_compiles_for_v5e_at_wiki_d(one_chip, build, kernel):
    """At d = 2.38M the compiled level holds no [n, d+1] array (a 64-query
    table would be 610 MB of f32)."""
    compiled = build(one_chip, D_WIKI)
    _assert_kernel(compiled, kernel)
    assert str(D_WIKI + 1) not in compiled.as_text()


def test_pregather_compiles_for_v5e(one_chip):
    def level(xg, vals, bc):
        return mscm_pregather(xg, vals, bc, interpret=False)

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = jax.jit(level).lower(
        s((A, R), jnp.float32), s((C, R, B), jnp.float32),
        s((A,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)
