"""Quantized serving tiers (ISSUE 9): storage, kernel parity, serving seams.

Pins the tier contract at every layer:

* storage — per-(chunk, column) symmetric scales bound the dequant error by
  ``scale / 2`` per weight (hypothesis property); the pruned re-pack keeps
  the heavy rows **bitwise** and only ever shrinks the pad width.
* kernel — ``mscm_pallas_grouped_q`` (in-register dequant) is bitwise what
  the exact grouped kernel returns on the dequantized f32 weights:
  quantization error comes from storage, never from the kernel.
* serving — ``tier="exact"`` stays bitwise the unquantized engine;
  ``tier="int8"`` results are topology-invariant (P, sync mode, in-process
  vs subprocess fleet) because quantization happens per partition *after*
  the split; the manifest records tier/dtype/compressed bytes (schema v2)
  and still reads v1 documents.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.core import XMRTree
from repro.index import ScatterGatherPlanner, partition_tree
from repro.index.partition import MANIFEST_VERSION, PartitionManifest
from repro.quant import (
    QuantizedTree,
    dequantize_layer,
    dequantize_tree,
    prune_chunks,
    quantize_index,
    quantize_layer,
    quantize_tree,
)
from repro.serving import PartitionConfig, QuantConfig, ServeConfig, XMRServingEngine
from repro.sparse import random_sparse_csr
from tests.conftest import make_tree_weights

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def quant_setup():
    rng = np.random.default_rng(29)
    d, B = 200, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    queries = random_sparse_csr(16, d, 15, rng)
    import jax.numpy as jnp

    xi, xv = map(jnp.asarray, queries.to_ell(32))
    return tree, queries, xi, xv


def _assert_bitwise(got, ref):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    s_got = np.asarray(got[0], np.float32)
    s_ref = np.asarray(ref[0], np.float32)
    assert np.array_equal(s_got.view(np.uint32), s_ref.view(np.uint32))


# ---------------------------------------------------------------------------
# 1. storage: scale math, error bound, pruned re-pack
# ---------------------------------------------------------------------------

def test_quantize_dequantize_error_bound(quant_setup):
    """Worst-case |dequant - original| <= scale / 2 per weight (int8)."""
    tree, *_ = quant_setup
    for lay in tree.layers:
        q = quantize_layer(lay)
        deq = dequantize_layer(q, d=tree.d)
        err = np.abs(
            np.asarray(deq.chunk_vals) - np.asarray(lay.chunk_vals)
        )
        bound = np.asarray(q.chunk_scales)[:, :, None] * (0.5 + 1e-5)
        assert (err <= bound).all()
        assert np.asarray(q.chunk_vals).dtype == np.int8
        # the ELL mask is never perturbed
        np.testing.assert_array_equal(
            np.asarray(q.chunk_rows), np.asarray(lay.chunk_rows)
        )


def test_zero_column_dequantizes_to_exact_zero(quant_setup):
    """All-zero columns take scale 1 (no 0/0) and reconstruct exactly 0."""
    tree, *_ = quant_setup
    lay = tree.layers[-1]
    vals = np.asarray(lay.chunk_vals).copy()
    vals[:, 0, :] = 0.0  # zero out one column per chunk
    q = quantize_layer(lay, vals=vals)
    scales = np.asarray(q.chunk_scales)
    assert (scales[:, 0] == 1.0).all()
    deq = np.asarray(dequantize_layer(q, d=tree.d).chunk_vals)
    assert (deq[:, 0, :] == 0.0).all()


def test_prune_chunks_keeps_heavy_rows_bitwise(quant_setup):
    tree, *_ = quant_setup
    lay = tree.layers[-1]
    rows = np.asarray(lay.chunk_rows)
    vals = np.asarray(lay.chunk_vals)
    keep_frac = 0.5
    new_rows, new_vals = prune_chunks(rows, vals, keep_frac, sentinel=tree.d)
    c, r_new = new_rows.shape
    assert r_new % 128 == 0 and r_new >= 128
    assert r_new <= rows.shape[1]
    for ci in range(c):
        valid = rows[ci] != tree.d
        nnz = int(valid.sum())
        expect_keep = int(np.ceil(keep_frac * nnz))
        got_valid = new_rows[ci] != tree.d
        assert int(got_valid.sum()) == expect_keep
        # survivors are exactly the top-|.| rows (stable: low index on ties)
        mag = np.where(valid, np.abs(vals[ci]).max(axis=0), -1.0)
        order = np.argsort(-mag, kind="stable")[:expect_keep]
        expect_rows = rows[ci][np.sort(order)]          # ascending row order
        np.testing.assert_array_equal(new_rows[ci][:expect_keep], expect_rows)
        # kept weights are bitwise the originals
        np.testing.assert_array_equal(
            new_vals[ci][:, :expect_keep], vals[ci][:, np.sort(order)]
        )
        # padding is sentinel/0
        assert (new_rows[ci][expect_keep:] == tree.d).all()
        assert (new_vals[ci][:, expect_keep:] == 0.0).all()


def test_prune_chunks_keep_frac_one_is_lossless(quant_setup):
    tree, *_ = quant_setup
    lay = tree.layers[0]
    rows = np.asarray(lay.chunk_rows)
    vals = np.asarray(lay.chunk_vals)
    new_rows, new_vals = prune_chunks(rows, vals, 1.0, sentinel=tree.d)
    for ci in range(rows.shape[0]):
        valid = rows[ci] != tree.d
        np.testing.assert_array_equal(new_rows[ci][: valid.sum()],
                                      rows[ci][valid])
        np.testing.assert_array_equal(new_vals[ci][:, : valid.sum()],
                                      vals[ci][:, valid])


def test_prune_chunks_rejects_bad_keep_frac(quant_setup):
    tree, *_ = quant_setup
    lay = tree.layers[0]
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="keep_frac"):
            prune_chunks(np.asarray(lay.chunk_rows),
                         np.asarray(lay.chunk_vals), bad, sentinel=tree.d)


def test_quantized_tree_cannot_be_resplit(quant_setup):
    tree, *_ = quant_setup
    qtree = quantize_tree(tree)
    with pytest.raises(TypeError, match="quantize per partition"):
        qtree.head(1)
    with pytest.raises(TypeError, match="quantize per partition"):
        qtree.extract(1, 0, 4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(
        c=st.integers(1, 4), r=st.integers(1, 12), b=st.integers(1, 8),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_error_bound_property(c, r, b, seed):
        """|dequant - v| <= scale/2 for arbitrary chunk tiles (int8)."""
        rng = np.random.default_rng(seed)
        vals = (rng.standard_normal((c, b, r)) *
                10.0 ** rng.integers(-3, 3)).astype(np.float32)
        lay = dataclasses.make_dataclass("L", ["chunk_rows", "chunk_vals"])(
            chunk_rows=np.zeros((c, r), np.int32), chunk_vals=vals,
        )
        q = quantize_layer(lay)
        scales = np.asarray(q.chunk_scales)
        deq = (np.asarray(q.chunk_vals).astype(np.float32)
               * scales[:, :, None])
        assert (np.abs(deq - vals) <= scales[:, :, None] * (0.5 + 1e-5)).all()
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_error_bound_property():
        pass


# ---------------------------------------------------------------------------
# 2. kernel: fused dequant == dequantize-then-exact, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", ["int8", "int8_pruned"])
def test_kernel_parity_bitwise(quant_setup, tier):
    tree, _, xi, xv = quant_setup
    qtree = quantize_tree(tree, tier=tier)
    ref = jax.block_until_ready(
        dequantize_tree(qtree).infer(
            xi, xv, beam=10, topk=5, method="mscm_pallas_grouped"
        )
    )
    got = jax.block_until_ready(
        qtree.infer(xi, xv, beam=10, topk=5, method="mscm_pallas_grouped_q")
    )
    _assert_bitwise(got, ref)


def test_int8_recall_close_to_exact(quant_setup):
    """Not bitwise — the tolerance contract: int8 recall@5 stays high."""
    from repro.quant import recall_at_k

    tree, _, xi, xv = quant_setup
    ref = tree.infer(xi, xv, beam=10, topk=5, method="mscm_pallas_grouped")
    qtree = quantize_tree(tree, tier="int8")
    got = qtree.infer(xi, xv, beam=10, topk=5,
                      method="mscm_pallas_grouped_q")
    assert recall_at_k(ref[1], got[1]) >= 0.9


# ---------------------------------------------------------------------------
# 3. serving: exact tier untouched, tier topology-invariance, config seams
# ---------------------------------------------------------------------------

def test_exact_tier_is_bitwise_unchanged(quant_setup):
    """The default tier serves the f32 tree exactly as before this PR."""
    tree, queries, xi, xv = quant_setup
    engine = XMRServingEngine(tree, ServeConfig(ell_width=32, max_batch=64))
    assert engine.config.tier == "exact"
    ref = tree.infer(xi, xv, beam=engine.config.beam,
                     topk=engine.config.topk, method=engine.method)
    _assert_bitwise(engine.serve_batch(queries), ref)


def test_int8_engine_unpartitioned(quant_setup):
    tree, queries, *_ = quant_setup
    engine = XMRServingEngine(
        tree, ServeConfig(ell_width=32, max_batch=64,
                          quant=QuantConfig(tier="int8")),
    )
    assert engine.method == "mscm_pallas_grouped_q"
    assert isinstance(engine.tree, QuantizedTree)
    s, l = engine.serve_batch(queries)
    assert s.shape == l.shape


def test_quant_tier_with_explicit_exact_method_raises(quant_setup):
    tree, *_ = quant_setup
    with pytest.raises(ValueError, match="mscm_pallas_grouped_q"):
        XMRServingEngine(
            tree, ServeConfig(ell_width=32, method="mscm_dense",
                              quant=QuantConfig(tier="int8")),
        )


@pytest.mark.parametrize("tier", ["int8", "int8_pruned"])
def test_tier_parity_across_topologies(quant_setup, tier):
    """Same bits from P=2/P=4 x level/pipelined: quantize-per-partition
    must not depend on how the label space is split or synced."""
    tree, _, xi, xv = quant_setup
    runs = []
    for p in (2, 4):
        qidx = quantize_index(partition_tree(tree, p), tier=tier)
        for sync in ("level", "pipelined"):
            pl = ScatterGatherPlanner(
                qidx, beam=10, topk=5,
                method="mscm_pallas_grouped_q", sync=sync,
            )
            runs.append(jax.block_until_ready(pl.infer(xi, xv)))
    for r in runs[1:]:
        _assert_bitwise(r, runs[0])


def test_quantconfig_validation():
    with pytest.raises(ValueError, match="tier"):
        QuantConfig(tier="int4")
    with pytest.raises(ValueError, match="prune_keep"):
        QuantConfig(tier="int8_pruned", prune_keep=0.0)


def test_serveconfig_flat_kwarg_shim():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        cfg = ServeConfig(tier="int8_pruned", prune_keep=0.25)
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert cfg.quant.tier == "int8_pruned"
    assert cfg.tier == "int8_pruned"          # flat read property
    assert cfg.quant.prune_keep == 0.25


# ---------------------------------------------------------------------------
# 4. manifest v2 + checkpoint round-trips
# ---------------------------------------------------------------------------

def test_manifest_v2_records_tier_and_compressed_bytes(quant_setup):
    tree, *_ = quant_setup
    idx = partition_tree(tree, 2)
    qidx = quantize_index(idx, tier="int8")
    m = qidx.manifest
    assert m.version == MANIFEST_VERSION == 2
    for info, qinfo in zip(idx.manifest.partitions, m.partitions):
        assert (info.tier, info.dtype) == ("exact", "float32")
        assert (qinfo.tier, qinfo.dtype) == ("int8", "int8")
        assert qinfo.memory_bytes < info.memory_bytes
        assert qinfo.content_hash != info.content_hash
    # round-trip preserves the tier columns
    again = PartitionManifest.from_json(m.to_json())
    assert again == m


def test_manifest_reads_v1_documents(quant_setup):
    """A pre-tier manifest (no tier/dtype rows) loads with exact defaults."""
    import json

    tree, *_ = quant_setup
    m = partition_tree(tree, 2).manifest
    doc = json.loads(m.to_json())
    doc["version"] = 1
    for row in doc["partitions"]:
        del row["tier"], row["dtype"]
    v1 = PartitionManifest.from_json(json.dumps(doc))
    assert v1.version == MANIFEST_VERSION
    assert all(p.tier == "exact" and p.dtype == "float32"
               for p in v1.partitions)
    with pytest.raises(ValueError, match="version"):
        PartitionManifest.from_json(json.dumps({**doc, "version": 99}))


def test_checkpoint_roundtrip_quantized_layers(quant_setup, tmp_path):
    """QuantLayerArrays survive the npy checkpoint path with int8 intact."""
    from repro.checkpoint import Checkpointer

    tree, *_ = quant_setup
    qtree = quantize_tree(tree, tier="int8")
    ckpt = Checkpointer(str(tmp_path), async_write=False)
    ckpt.save(0, {"layers": qtree.layers})
    step, out = ckpt.restore({"layers": qtree.layers})
    assert step == 0
    restored = QuantizedTree(
        layers=out["layers"], n_cols=qtree.n_cols,
        branching=qtree.branching, d=qtree.d, tier=qtree.tier,
    )
    for a, b in zip(qtree.layers, restored.layers):
        assert np.asarray(b.chunk_vals).dtype == np.int8
        np.testing.assert_array_equal(np.asarray(a.chunk_vals),
                                      np.asarray(b.chunk_vals))
        np.testing.assert_array_equal(np.asarray(a.chunk_scales),
                                      np.asarray(b.chunk_scales))
        np.testing.assert_array_equal(np.asarray(a.chunk_rows),
                                      np.asarray(b.chunk_rows))


# ---------------------------------------------------------------------------
# 5. fleet: subprocess parity + the fp8 wire guard
# ---------------------------------------------------------------------------

def test_fleet_int8_bitwise_vs_in_process(quant_setup):
    """The acceptance pin: tier="int8" through real worker subprocesses
    returns exactly the in-process quantized engine's bits."""
    from repro.serving.fleet import PartitionFleet

    tree, queries, *_ = quant_setup
    cfg = ServeConfig(
        ell_width=32, max_batch=64,
        partition=PartitionConfig(partitions=2, partition_sync="pipelined"),
        quant=QuantConfig(tier="int8"),
    )
    ref_engine = XMRServingEngine(tree, cfg)
    assert all(p.tier == "int8" for p in ref_engine.index.manifest.partitions)
    ref = ref_engine.serve_batch(queries)

    engine = XMRServingEngine(tree, cfg)
    with PartitionFleet.launch(2, rpc_timeout_s=120.0) as fleet:
        fleet.attach(engine)
        got = engine.serve_batch(queries)
    _assert_bitwise(got, ref)


def test_fleet_rejects_fp8_wire(quant_setup):
    """fp8 serves in-process only: numpy dtype strings cannot carry
    ml_dtypes over the RPC wire, so shipping it must fail loudly."""
    from repro.serving.fleet.launcher import partition_payload

    tree, *_ = quant_setup
    qidx = quantize_index(partition_tree(tree, 2), tier="fp8")
    with pytest.raises(ValueError, match="int8"):
        partition_payload(qidx, 0, beam=10, topk=5,
                          method="mscm_pallas_grouped_q",
                          score_mode="prod", qt=8)


def test_fp8_tier_in_process(quant_setup):
    tree, _, xi, xv = quant_setup
    qtree = quantize_tree(tree, tier="fp8")
    ref = dequantize_tree(qtree).infer(
        xi, xv, beam=10, topk=5, method="mscm_pallas_grouped"
    )
    got = qtree.infer(xi, xv, beam=10, topk=5,
                      method="mscm_pallas_grouped_q")
    _assert_bitwise(got, ref)
