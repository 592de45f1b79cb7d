"""End-to-end serving driver (the paper's deployment scenario, §6).

Builds a product-search model at enterprise *geometry* (d = 4M features,
L = 32^4 ≈ 1.05M labels, branching 32 — the paper's tree shape scaled from
100M to what a CPU container holds), then drives the serving stack in both
production settings:

* **batch** — ``serve_batch`` (double-buffered chunk dispatch), Table-4
  panel per masked-matmul method;
* **online** — a Poisson request stream through the async
  :class:`~repro.serving.MicroBatcher`, reporting queue-wait vs compute
  split and throughput alongside the blocking per-query baseline;
* **network** — ``--gateway PORT`` serves the model over HTTP (stdlib
  :class:`~repro.serving.ServingGateway`); with ``--partitions P`` the
  engine runs against a cross-process worker fleet exchanging beams over
  the socket RPC. Demo queries are driven through real HTTP requests and a
  curl recipe is printed for poking the running server.

``--tier int8`` (or ``int8_pruned`` / ``fp8``) serves a compressed storage
tier (:mod:`repro.quant`): per-partition memory shrinks several-fold and
the printed manifest shows the compressed bytes + tier/dtype columns;
quality vs the exact tier is reported as recall instead of bitwise parity.

    PYTHONPATH=src python examples/serve_search.py [--queries 256] [--small]
    PYTHONPATH=src python examples/serve_search.py --small --gateway 8080 \\
        [--partitions 2] [--tier int8]
"""

import argparse
import json
import os
import sys
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks/
from benchmarks.common import build_benchmark_tree
from repro.compile_cache import enable_compile_cache
from repro.data.xmr_data import XMRShape, benchmark_queries
from repro.serving import (
    BatchPolicy,
    MicroBatcher,
    PartitionConfig,
    QuantConfig,
    Query,
    QueryResult,
    ServeConfig,
    ServingGateway,
    XMRServingEngine,
)
from repro.serving.config import QUANT_TIERS


def main() -> None:
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--beam", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=16,
                    help="micro-batcher coalescing size")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--small", action="store_true",
                    help="32k labels / d=337k (fast demo)")
    ap.add_argument("--partitions", type=int, default=1,
                    help="label-space partitions (scatter-gather index; "
                         "per-device model bytes shrink ~1/P, results stay "
                         "bitwise-identical)")
    ap.add_argument("--gateway", type=int, default=None, metavar="PORT",
                    help="serve over HTTP on this port (0 = ephemeral); "
                         "with --partitions > 1 the engine runs against a "
                         "cross-process worker fleet")
    ap.add_argument("--tier", default="exact", choices=QUANT_TIERS,
                    help="weight storage tier (repro.quant): int8 / "
                         "int8_pruned cut per-partition memory several-"
                         "fold; fp8 is in-process only (no fleet wire)")
    args = ap.parse_args()
    if args.tier == "fp8" and args.gateway is not None and args.partitions > 1:
        ap.error("--tier fp8 cannot ship over the fleet RPC wire; "
                 "use --tier int8 with --partitions > 1")

    if args.small:
        shape = XMRShape("search-32k", 337_067, 32_768, 10_000, 100, 64)
    else:
        shape = XMRShape("search-1m", 4_000_000, 32**4, 10_000, 150, 64)
    rng = np.random.default_rng(0)

    print(f"building model: L={shape.L:,} labels, d={shape.d:,} ...")
    t0 = time.time()
    tree = build_benchmark_tree(shape, 32, rng)
    print(f"  built in {time.time() - t0:.0f}s, "
          f"{tree.memory_bytes() / 1e9:.2f} GB chunked weights, "
          f"depth {tree.depth}")

    queries = benchmark_queries(shape, args.queries, rng)

    if args.gateway is not None:
        serve_gateway(tree, queries, args)
        return
    if args.partitions > 1:
        serve_partitioned(tree, queries, shape, args)
        return

    print("\n== batch setting (Table 4 panel) ==")
    # "auto" is the backend's serving path (the grouped Pallas kernel on a
    # TPU, mscm_dense elsewhere). A non-exact tier forces the quantized
    # kernel, so the per-method panel collapses to the single tier method.
    methods = (("auto", "mscm_searchsorted", "vanilla")
               if args.tier == "exact" else ("auto",))
    for method in methods:
        eng = XMRServingEngine(
            tree,
            ServeConfig(beam=args.beam, topk=10, method=method,
                        ell_width=256, max_batch=64,
                        quant=QuantConfig(tier=args.tier)),
        )
        eng.warmup(shape.d, batch_sizes=(64,))
        t0 = time.time()
        scores, labels = eng.serve_batch(queries)
        wall = time.time() - t0
        s = eng.latency_summary()["amortized"]
        print(f"{eng.method:20s} amortized {s['avg_ms_per_query']:7.3f} ms/q "
              f"over {s['queries']} queries "
              f"({wall:.1f}s wall; per-query percentiles are an online-"
              f"setting metric)")

    print("\n== online setting (async micro-batching) ==")
    eng = XMRServingEngine(
        tree, ServeConfig(
            beam=args.beam, topk=10, method="auto",
            ell_width=256, max_batch=64,
            quant=QuantConfig(tier=args.tier)))
    eng.warmup_buckets(shape.d, args.max_batch)

    n = min(args.queries, 128)
    t0 = time.perf_counter()
    eng.serve_online(queries, limit=n)
    base_qps = n / (time.perf_counter() - t0)
    print(f"{'per-query baseline':24s} {base_qps:8.1f} QPS (blocking loop)")

    mb = MicroBatcher(eng, BatchPolicy(args.max_batch, args.max_wait_ms))
    mb.start()
    futs = []
    for i in range(n):  # Poisson arrivals at 2x the baseline's capacity
        time.sleep(rng.exponential(1.0 / (2.0 * base_qps)))
        futs.append(mb.submit(*queries.row(i)))
    for f in futs:
        f.result(timeout=300)
    mb.stop()
    print(mb.metrics.table4_row(f"microbatch-{args.max_batch}"))

    print("\n(paper Table 4 at 100M labels on a single x86 thread: "
          "0.88 ms MSCM vs 7.28 ms vanilla — an 8x ratio; compare the ratios.)")


def serve_partitioned(tree, queries, shape, args) -> None:
    """Scatter-gather demo: the label space split P ways, end to end.

    Shows the manifest (per-partition label ranges + memory), then serves
    the same stream through the unpartitioned engine and the partitioned
    one and checks bitwise identity — the paper's enterprise scenario
    (a tree bigger than one device) without changing a single result bit.
    """
    p = args.partitions
    print(f"\n== partitioned serving (scatter-gather, P={p}) ==")
    ref = XMRServingEngine(
        tree, ServeConfig(beam=args.beam, topk=10, max_batch=64))
    ref_s, ref_l = ref.serve_batch(queries)

    engine = XMRServingEngine(
        tree, ServeConfig(beam=args.beam, topk=10, max_batch=64,
                          partition=PartitionConfig(partitions=p),
                          quant=QuantConfig(tier=args.tier)))
    m = engine.index.manifest
    print(f"split level {m.level}; router {m.router_memory_bytes / 1e6:.1f} MB"
          f" (replicated); per-device max "
          f"{m.max_partition_bytes() / 1e6:.1f} MB of "
          f"{m.total_memory_bytes / 1e6:.1f} MB total "
          f"({m.shrink_ratio():.2f}x shrink)")
    for info in m.partitions:
        print(f"  partition {info.pid}: labels [{info.label_start:>9,}, "
              f"{info.label_end:>9,})  {info.memory_bytes / 1e6:7.1f} MB  "
              f"tier {info.tier}/{info.dtype}  hash {info.content_hash}")

    mb = MicroBatcher(engine, BatchPolicy(args.max_batch, args.max_wait_ms))
    with mb:
        res = [f.result(timeout=600) for f in mb.submit_csr(queries)]
    s = np.stack([r[0] for r in res])
    l = np.stack([r[1] for r in res])
    if args.tier == "exact":
        identical = np.array_equal(s, ref_s) and np.array_equal(l, ref_l)
        print(f"\nbitwise-identical to unpartitioned: {identical}")
    else:
        from repro.quant import recall_at_k, score_mae

        print(f"\nquantized tier '{args.tier}' vs exact: "
              f"recall@10 {recall_at_k(ref_l, l):.4f}, "
              f"score MAE {score_mae(ref_s, s, 10):.5f}")
    summ = mb.metrics.summary()
    print(f"partition occupancy (share of top-k per partition): "
          f"{summ.get('partition_occupancy')}")
    print(mb.metrics.table4_row(f"partitioned-P{p}"))


def serve_gateway(tree, queries, args) -> None:
    """Serve the model over HTTP — in-process or against a worker fleet.

    With ``--partitions P`` the engine's per-level merge runs against P
    worker *subprocesses* (``repro.serving.fleet``) exchanging beams over a
    socket RPC; the gateway answers with results bitwise-identical to the
    in-process engine either way. Demo traffic goes through real HTTP
    requests so the printed numbers include the network edge.
    """
    p = args.partitions
    quant = QuantConfig(tier=args.tier)
    cfg = ServeConfig(beam=args.beam, topk=10, max_batch=64, quant=quant)
    if p > 1:
        cfg = ServeConfig(
            beam=args.beam, topk=10, max_batch=64, quant=quant,
            partition=PartitionConfig(partitions=p,
                                      partition_sync="pipelined"),
        )
    engine = XMRServingEngine(tree, cfg)

    fleet = None
    if p > 1:
        from repro.serving.fleet import PartitionFleet

        print(f"\nlaunching {p} partition workers ...")
        fleet = PartitionFleet.launch(p).attach(engine)
        print(f"  workers up: {fleet.ping()}")

    try:
        mb = MicroBatcher(engine,
                          BatchPolicy(args.max_batch, args.max_wait_ms))
        with mb, ServingGateway(mb, port=args.gateway, fleet=fleet) as gw:
            print(f"\n== HTTP gateway on {gw.url} ==")
            print(f"  POST {gw.url}/v1/query   "
                  '{"v": 1, "idx": [...], "val": [...]}')
            print(f"  GET  {gw.url}/healthz    GET  {gw.url}/metrics")
            print("  curl example:")
            idx, val = queries.row(0)
            wire = Query(idx=idx[:3], val=val[:3]).to_wire()
            print(f"    curl -s {gw.url}/v1/query -d '{json.dumps(wire)}'")

            n = min(args.queries, 64)
            t0 = time.perf_counter()
            for i in range(n):
                idx, val = queries.row(i)
                req = urllib.request.Request(
                    gw.url + "/v1/query",
                    data=json.dumps(Query(idx=idx, val=val,
                                          qid=i).to_wire()).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=300) as resp:
                    res = QueryResult.from_wire(json.load(resp))
                assert res.ok and res.qid == i
            wall = time.perf_counter() - t0
            print(f"\nserved {n} queries over HTTP in {wall:.1f}s "
                  f"({n / wall:.1f} QPS incl. network edge)")
            with urllib.request.urlopen(gw.url + "/metrics",
                                        timeout=30) as resp:
                summ = json.load(resp)
            print(f"avg_batch={summ.get('avg_batch', 0):.1f} "
                  f"p50={summ.get('p50_ms', 0):.2f}ms "
                  f"p99={summ.get('p99_ms', 0):.2f}ms")
            if fleet is not None:
                print(f"partition occupancy: "
                      f"{summ.get('partition_occupancy')}")
    finally:
        if fleet is not None:
            fleet.close()


if __name__ == "__main__":
    main()
