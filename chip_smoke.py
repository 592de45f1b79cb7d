"""Smoke run of the serving path on a TPU, at amazon-670k's published width.

    python chip_smoke.py [--seed N]    # one chip: the in-process main path
    python chip_smoke.py --chips 4     # four chips: partitioned serving only

One chip: builds the amazon-670k tree (paper Table 5: d = 135,909,
L = 670,091, branching 32, 64 nnz per ranker column; 2.2 GB of chunk tiles)
from ``--seed``, serves a 64-query batch through ``serve_batch`` and 16
online queries through a started ``MicroBatcher``, both on
``method="auto"`` (the compiled grouped Pallas kernel), and checks them
against a numpy beam search over the same CSC weights (f32 inputs, f64
sums). Four chips: the same tree cut into four pipelined label partitions,
one per chip, served through the ``MicroBatcher`` and compared bitwise
with the unpartitioned engine on one chip.

Exits nonzero unless a TPU is present. The last output line is one JSON
object naming the device; earlier lines carry informational wall times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

BRANCHING, BEAM, TOPK = 32, 10, 10
N_BATCH, N_ONLINE, N_REF = 64, 16, 8
# Device scores against the f64-summed reference: a few f32 roundings per
# level over four levels. A bf16 matmul pass would miss this by ~100x.
RTOL = 1e-5
# Reference candidates closer than this may legitimately swap places.
TIE_RTOL = 2 * RTOL


def require_tpu(count: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU present (JAX backend: "
            f"{devices[0].platform}); this check runs only on a chip"
        )
    if len(devices) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} TPU devices; "
                 f"JAX sees {len(devices)}")
    return devices


def path_queries(weights, n_labels: int, n: int, nnz: int,
                 rng: np.random.Generator):
    """Queries that reach into the tree: each draws most of its features
    from the supports of one random label's ancestor columns, the rest
    Zipf-distributed like :func:`repro.data.xmr_data.benchmark_queries`.

    Random weights have uniform supports over d, so a pure Zipf query
    meets almost none of them and nearly every logit would be exactly 0.
    """
    from repro.sparse import CSR

    d = weights[0].shape[0]
    per_level = nnz // (2 * len(weights))
    rows_i, rows_v = [], []
    for _ in range(n):
        leaf = int(rng.integers(n_labels))
        feats = []
        for li, w in enumerate(weights):
            col = leaf // BRANCHING ** (len(weights) - 1 - li)
            support = w.indices[w.indptr[col]:w.indptr[col + 1]]
            k = min(per_level, len(support))
            feats.append(rng.choice(support, size=k, replace=False))
        zipf = (rng.zipf(1.3, size=4 * nnz) - 1) % d
        feats.append(zipf[: nnz - sum(len(f) for f in feats)])
        idx = np.unique(np.concatenate(feats)).astype(np.int32)
        rows_i.append(idx)
        rows_v.append((np.abs(rng.standard_normal(len(idx))) + 0.05)
                      .astype(np.float32))
    return CSR.from_rows(rows_i, rows_v, (n, d))


def reference_search(weights, idx, val, beam: int, topk: int):
    """Beam search (paper Alg. 1, ``prod`` scores) for one query.

    Plain numpy over the CSC weights, independent of the served tree's
    chunk layout and kernels: f32 inputs, f64 sums. Returns
    ``(scores, labels, ambiguous)`` for the top ``topk + 1`` candidates;
    ``ambiguous`` is True when a beam cut falls between two candidates
    closer than ``TIE_RTOL`` (but not equal), where f32 rounding may pick
    either side.
    """
    d = weights[0].shape[0]
    x = np.zeros(d, np.float64)
    x[idx] = val
    parents, scores = np.zeros(1, np.int64), np.ones(1, np.float64)
    ambiguous = False
    for li, w in enumerate(weights):
        last = li == len(weights) - 1
        cols = (parents[:, None] * BRANCHING + np.arange(BRANCHING)).ravel()
        par = np.repeat(scores, BRANCHING)
        real = cols < w.shape[1]
        cols, par = cols[real], par[real]
        logits = np.array([
            x[w.indices[w.indptr[c]:w.indptr[c + 1]]]
            @ w.data[w.indptr[c]:w.indptr[c + 1]].astype(np.float64)
            for c in cols
        ])
        s = par / (1.0 + np.exp(-logits))
        order = np.lexsort((cols, -s))           # score desc, id asc
        k = min(topk if last else beam, len(cols))
        if k < len(cols):
            hi, lo = s[order[k - 1]], s[order[k]]
            ambiguous |= bool(0 < hi - lo <= TIE_RTOL * hi)
        keep = order[: k + 1] if last else order[:k]
        parents, scores = cols[keep], s[keep]
    return scores, parents, ambiguous


def check_against_reference(weights, queries, got_s, got_l):
    """Assert served results match :func:`reference_search`. Returns the
    number of queries checked (ambiguous ones are reported and skipped) and
    the largest relative score error seen."""
    checked, worst = 0, 0.0
    for q in range(got_s.shape[0]):
        idx, val = queries.row(q)
        ref_s, ref_l, ambiguous = reference_search(weights, idx, val, BEAM, TOPK)
        if ambiguous:
            print(f"reference: query {q} has a near-tie at a beam cut; skipped")
            continue
        # Positions joined by near-ties may hold their labels in any order.
        start = 0
        for i in range(1, TOPK + 1):
            near = i < TOPK and ref_s[i - 1] - ref_s[i] <= TIE_RTOL * ref_s[i - 1]
            if not near:
                if set(got_l[q, start:i]) != set(ref_l[start:i]):
                    raise AssertionError(
                        f"query {q}: labels {got_l[q].tolist()} != "
                        f"reference {ref_l[:TOPK].tolist()}"
                    )
                start = i
        by_label = dict(zip(ref_l.tolist(), ref_s.tolist()))
        want = np.array([by_label[int(l)] for l in got_l[q]])
        np.testing.assert_allclose(got_s[q], want, rtol=RTOL,
                                   err_msg=f"query {q} scores")
        worst = max(worst, float(np.max(np.abs(got_s[q] - want) / want)))
        checked += 1
    if checked < got_s.shape[0] // 2:
        raise AssertionError(f"only {checked} queries were checkable")
    return checked, worst


def build(seed: int):
    from benchmarks.common import build_benchmark_weights
    from repro.core import XMRTree
    from repro.data.xmr_data import PAPER_SHAPES

    shape = PAPER_SHAPES["amazon-670k"]
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    weights = build_benchmark_weights(shape, BRANCHING, rng)
    tree = XMRTree.from_weight_matrices(weights, BRANCHING)
    queries = path_queries(weights, shape.L, N_BATCH, shape.query_nnz, rng)
    print(f"build_s={time.perf_counter() - t0:.3f} dataset={shape.name} "
          f"d={shape.d} L={shape.L} leaf_slots={tree.n_labels} "
          f"depth={tree.depth} R={tree.layers[-1].chunk_rows.shape[1]} "
          f"tree_bytes={tree.memory_bytes()}")
    return weights, tree, queries


def device_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use', 'not reported')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")


def assert_compiled_kernels(engine) -> None:
    from repro.kernels import ops

    assert engine.method == "mscm_pallas_grouped", engine.method
    assert ops._auto_interpret(None) is False, "Pallas kernels would be interpreted"


def check_results(scores, labels, n: int, n_labels: int) -> None:
    assert scores.shape == (n, TOPK) and labels.shape == (n, TOPK)
    assert np.isfinite(scores).all(), "non-finite scores"
    assert ((labels >= 0) & (labels < n_labels)).all(), "label out of range"


def smoke_one_chip(seed: int, device) -> None:
    from repro.serving import (
        BatchPolicy, MicroBatcher, ServeConfig, XMRServingEngine,
    )

    weights, tree, queries = build(seed)
    print(f"device after build: {device_bytes(device)}")
    engine = XMRServingEngine(tree, ServeConfig(
        method="auto", beam=BEAM, topk=TOPK, max_batch=N_BATCH))
    assert_compiled_kernels(engine)

    t0 = time.perf_counter()
    engine.warmup(tree.d, batch_sizes=(N_BATCH,))
    t_batch_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    got_s, got_l = engine.serve_batch(queries)
    t_batch = time.perf_counter() - t0
    check_results(got_s, got_l, N_BATCH, tree.n_labels)

    mb = MicroBatcher(engine, BatchPolicy(max_batch=N_ONLINE, max_wait_ms=2.0))
    t0 = time.perf_counter()
    mb.start()                                  # warms buckets 1..16
    t_online_compile = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        futs = [mb.submit(*queries.row(i)) for i in range(N_ONLINE)]
        online = [f.result(timeout=300) for f in futs]
        t_online = time.perf_counter() - t0
    finally:
        mb.stop()
    on_s = np.stack([r[0] for r in online])
    on_l = np.stack([r[1] for r in online])
    # Within one method results are bitwise stable across batch sizes.
    np.testing.assert_array_equal(on_l, got_l[:N_ONLINE])
    np.testing.assert_array_equal(on_s, got_s[:N_ONLINE])

    t0 = time.perf_counter()
    checked, worst = check_against_reference(
        weights, queries, got_s[:N_REF], got_l[:N_REF])
    t_ref = time.perf_counter() - t0
    print(f"compile_s batch={t_batch_compile:.3f} online={t_online_compile:.3f}")
    print(f"serve_s batch{N_BATCH}={t_batch:.3f} online{N_ONLINE}={t_online:.3f}")
    print(f"reference: {checked}/{N_REF} queries agree (rtol={RTOL}, "
          f"worst relative error {worst:.3e}) in {t_ref:.3f}s; "
          f"online == batch bitwise")
    print(f"device after serving: {device_bytes(device)}")


def smoke_four_chips(seed: int, devices) -> None:
    from repro.serving import (
        BatchPolicy, MicroBatcher, PartitionConfig, ServeConfig,
        XMRServingEngine,
    )

    _, tree, queries = build(seed)
    base = dict(method="auto", beam=BEAM, topk=TOPK, max_batch=N_BATCH)
    ref = XMRServingEngine(tree, ServeConfig(**base))
    assert_compiled_kernels(ref)
    t0 = time.perf_counter()
    ref_s, ref_l = ref.serve_batch(queries)
    print(f"unpartitioned_s={time.perf_counter() - t0:.3f} (incl. compile)")
    check_results(ref_s, ref_l, N_BATCH, tree.n_labels)

    engine = XMRServingEngine(tree, ServeConfig(**base, partition=PartitionConfig(
        partitions=4, partition_sync="pipelined")))
    assert_compiled_kernels(engine)
    homes = []
    for part in engine.planner.parts:
        held = {dev for lay in part.layers for dev in lay.chunk_vals.devices()}
        assert len(held) == 1, f"a partition spans {held}"
        homes.append(held.pop())
    assert len(set(homes)) == 4, f"partitions share devices: {homes}"
    print("partition devices: " + " ".join(str(d.id) for d in homes))

    t0 = time.perf_counter()
    with MicroBatcher(engine, BatchPolicy(max_batch=N_ONLINE, max_wait_ms=2.0)) as mb:
        res = [f.result(timeout=600) for f in mb.submit_csr(queries)]
    print(f"partitioned_s={time.perf_counter() - t0:.3f} (incl. compile)")
    got_s = np.stack([r[0] for r in res])
    got_l = np.stack([r[1] for r in res])
    np.testing.assert_array_equal(got_l, ref_l)
    np.testing.assert_array_equal(got_s, ref_s)
    print(f"partitioned P=4 pipelined == unpartitioned bitwise "
          f"({N_BATCH} queries)")
    for dev in devices[:4]:
        print(f"device {dev.id}: {device_bytes(dev)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(HERE)}")
    if args.chips == 4:
        smoke_four_chips(args.seed, devices)
    else:
        smoke_one_chip(args.seed, devices[0])
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
