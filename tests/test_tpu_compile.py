"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and Mosaic refuses here what it would refuse on the chip (block
tiling, VMEM limits). Widths are amazon-670k's (paper Table 5) at branching
32: R = 496 rows per chunk padded to 512 lanes, B = 32 columns, chunk tiles
stored ``[C, B, R]``, a 64-query bucket at beam 10, query tiles of 8,
queries in ELL rows of 256; the grouped levels also compile at wiki-500k's
feature dimension, which no dense table reaches, and at amazon-670k's leaf
of 20,941 chunks, where the compiled program must read the stored tiles in
place: no copy of the tile array and no temporaries of its size. Every
call passes ``interpret=False`` explicitly (or, for the whole beam search,
patches the interpret choice), since the backend here is the CPU.

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
from repro.quant.kernels import mscm_grouped_q_level

D = 135_909          # amazon-670k feature dimension
D_WIKI = 2_381_304   # wiki-500k feature dimension
Q = 256              # ELL width of a query (ServeConfig.ell_width)
C, R, B = 1024, 512, 32
C_LEAF = 20_941      # amazon-670k leaf chunks
LEVEL_SIZES = (21, 655, 20_941, 670_091)   # amazon-670k, branching 32
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


def _tile_copies(text, c, b, r):
    """HLO lines that copy an array of a chunk tile array's shape, in
    either axis order."""
    shapes = "|".join(rf"{c},{x},{y}" for x, y in ((b, r), (r, b)))
    return re.findall(rf"= \w+\[(?:{shapes})\]\{{[^}}]*\}} copy\(", text)


def _compile_grouped(sharding, d, c=C):
    def level(xi, xv, rows, vals, bq, bc, ps):
        return ops.mscm_grouped_level(
            xi, xv, d, rows, vals, bq, bc, ps, qt=QT, mode="prod",
            interpret=False,
        )

    s = lambda shape, dt: _spec(sharding, shape, dt)  # noqa: E731
    return jax.jit(level).lower(
        s((N, Q), jnp.int32), s((N, Q), jnp.float32), s((c, R), jnp.int32),
        s((c, B, R), jnp.float32), s((A,), jnp.int32), s((A,), jnp.int32),
        s((A,), jnp.float32),
    ).compile()


def _compile_grouped_int8(sharding, d, c=C):
    def level(xi, xv, rows, vals, scales, bq, bc, ps):
        return mscm_grouped_q_level(
            xi, xv, d, rows, vals, scales, bq, bc, ps, qt=QT, mode="prod",
            interpret=False,
        )

    s = lambda shape, dt: _spec(sharding, shape, dt)  # noqa: E731
    return jax.jit(level).lower(
        s((N, Q), jnp.int32), s((N, Q), jnp.float32), s((c, R), jnp.int32),
        s((c, B, R), jnp.int8), s((c, B), jnp.float32), s((A,), jnp.int32),
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


@pytest.mark.parametrize(
    "build, kernel, itemsize",
    [(_compile_grouped, "mscm_grouped", 4),
     (_compile_grouped_int8, "mscm_grouped_q", 1)],
    ids=["exact", "int8"],
)
def test_grouped_leaf_reads_tiles_in_place(one_chip, build, kernel, itemsize):
    """At amazon-670k's leaf (20,941 chunks of [32, 512]) the compiled
    level reads the stored tiles where they lie: no copy of the tile array,
    and temporaries below one tile array's bytes. (Tiles stored
    ``[C, R, B]`` were copied on every call into a lane-padded layout:
    5.36 GB of temporaries against a 1.33 GB array.)"""
    compiled = build(one_chip, D, c=C_LEAF)
    _assert_kernel(compiled, kernel)
    assert not _tile_copies(compiled.as_text(), C_LEAF, B, R)
    tile_bytes = C_LEAF * B * R * itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < tile_bytes


def test_tree_infer_reads_tiles_in_place(one_chip, monkeypatch):
    """The whole grouped beam search at amazon-670k's four levels and a
    256-query bucket holds no copy of any level's tile array."""
    from repro.core.tree import TreeLayerArrays, _tree_infer

    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    chunks = (1,) + LEVEL_SIZES[:-1]
    layers = tuple(
        TreeLayerArrays(
            chunk_rows=s((c, R), jnp.int32),
            chunk_vals=s((c, B, R), jnp.float32),
            col_rows=s((c * B, 64), jnp.int32),
            col_vals=s((c * B, 64), jnp.float32),
        )
        for c in chunks
    )
    n = 256

    def search(layers, xi, xv):
        return _tree_infer(
            layers, LEVEL_SIZES, (B,) * len(LEVEL_SIZES), D, xi, xv,
            beam=BEAM, topk=BEAM, method="mscm_pallas_grouped",
            score_mode="prod", qt=QT,
        )

    compiled = jax.jit(search).lower(
        layers, s((n, Q), jnp.int32), s((n, Q), jnp.float32)
    ).compile()
    text = compiled.as_text()
    _assert_kernel(compiled, "mscm_grouped")
    for c in chunks:
        assert not _tile_copies(text, c, B, R), c
    tile_bytes = C_LEAF * B * R * 4
    assert compiled.memory_analysis().temp_size_in_bytes < tile_bytes
