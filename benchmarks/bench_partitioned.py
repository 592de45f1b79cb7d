"""Label-partitioned scatter–gather serving vs the unpartitioned tree.

The enterprise claim (ISSUE 4): at 100M labels no single device holds the
tree, so ``repro.index`` splits the label space P ways. This benchmark pins
the things that make that deployable:

* ``partition_parity`` — the planner's per-level sync mode returns
  **bitwise-identical** scores and labels for every MSCM method. A
  structural flag ``check_regression`` gates hard.
* ``pipelined_parity`` — the overlapped ``sync="pipelined"`` mode (ISSUE 5:
  speculative next-level expansion reconciled against the canonical select)
  is *also* bitwise-identical, per method. Gated hard.
* ``cache_parity`` — a hot-beam cache **hit** (second pass over the same
  router beams) returns bits identical to the cold pass. Gated hard.
* ``partition_memory_balanced`` — the manifest's per-partition
  ``memory_bytes`` shrink ~1/P (within slack for the phantom pad chunk and
  the ragged tail) and the LPT placement balances columns. Also gated.

Timing rows report the scatter–gather overhead (per-level candidate
exchange) against single-tree inference on the same device — the price of
fitting a tree P× bigger than the device — and the pipelined mode's
speedup over level sync.

``--multidevice`` (CI runs it under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) instead drives
``ServeConfig(partitions=2, shards=2)`` through the ``MicroBatcher`` on a
real (2 data × 2 model) mesh — level and pipelined sync — and emits an
``overlap_speedup`` structural flag: with partitions on their own devices,
pipelined throughput must be no worse than level-sync (the whole point of
taking the exchange off the matmul's critical path).

Run: ``python -m benchmarks.bench_partitioned [--n 48] [--partitions 2 4]
[--multidevice] [--json PATH]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List

import jax
import numpy as np

from benchmarks.common import build_benchmark_tree, csv_line, time_fn
from repro.data.xmr_data import PAPER_SHAPES, benchmark_queries, scaled_shape
from repro.index import ScatterGatherPlanner, partition_tree, place

# Relative tolerance for the overlap gate: pipelined must be at least this
# close to level-sync throughput (it shares the arithmetic; only the
# exchange schedule differs, so parity-of-throughput is a floor, and on
# shared CI runners we leave headroom for timer noise).
OVERLAP_TOLERANCE = 1.15


def _build(max_labels: int, seed: int):
    shape = PAPER_SHAPES["eurlex-4k"]
    if shape.L > max_labels:
        shape = scaled_shape(shape, max_labels / shape.L)
    rng = np.random.default_rng(seed)
    tree = build_benchmark_tree(shape, 16, rng)
    return shape, tree, rng


def _bitwise(got, ref) -> bool:
    return bool(
        np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
        and np.array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    )


def run(
    *,
    n_queries: int = 48,
    max_labels: int = 4096,
    partitions=(2, 4),
    methods=("mscm_dense", "mscm_pallas_grouped"),
    beam: int = 10,
    topk: int = 10,
    seed: int = 0,
) -> List[str]:
    shape, tree, rng = _build(max_labels, seed)
    queries = benchmark_queries(shape, n_queries, rng)
    import jax.numpy as jnp

    xi, xv = map(jnp.asarray, queries.to_ell(256))
    lines = []
    for p in partitions:
        idx = partition_tree(tree, p)
        m = idx.manifest

        # -- memory: the whole point — per-device bytes shrink ~1/P --------
        # Slack covers the phantom pad chunk per level and the ragged tail.
        balanced = m.max_partition_bytes() <= 1.5 * m.total_memory_bytes / p
        lines.append(
            csv_line(
                f"{shape.name}/partitioned/P{p}-memory",
                m.max_partition_bytes() / 1e3,  # kB, reported not gated
                f"partition_memory_balanced={balanced} "
                f"max_part_kb={m.max_partition_bytes() / 1e3:.0f} "
                f"total_kb={m.total_memory_bytes / 1e3:.0f} "
                f"router_kb={m.router_memory_bytes / 1e3:.1f} "
                f"shrink={m.shrink_ratio():.2f}x level={m.level}",
            )
        )

        for method in methods:
            ref = tree.infer(xi, xv, beam=beam, topk=topk, method=method)
            ref = jax.block_until_ready(ref)
            planner = ScatterGatherPlanner(
                idx, beam=beam, topk=topk, method=method
            )
            parity = _bitwise(jax.block_until_ready(planner.infer(xi, xv)), ref)
            t_ref = time_fn(
                lambda: tree.infer(
                    xi, xv, beam=beam, topk=topk, method=method
                )
            )
            t_part = time_fn(lambda: planner.infer(xi, xv))
            planner.profile(xi, xv)  # warm the per-partition path
            prof = planner.profile(xi, xv)
            lines.append(
                csv_line(
                    f"{shape.name}/partitioned/P{p}-{method}",
                    1e6 * t_part / n_queries,
                    f"partition_parity={parity} "
                    f"overhead={t_part / t_ref:.2f}x "
                    f"part_ms={'/'.join(f'{t:.1f}' for t in prof)}",
                )
            )

            # -- pipelined (ISSUE 5): overlapped exchange, still bitwise ---
            pipe = ScatterGatherPlanner(
                idx, beam=beam, topk=topk, method=method, sync="pipelined"
            )
            pipe_parity = _bitwise(
                jax.block_until_ready(pipe.infer(xi, xv)), ref
            )
            t_pipe = time_fn(lambda: pipe.infer(xi, xv))
            lines.append(
                csv_line(
                    f"{shape.name}/pipelined/P{p}-{method}",
                    1e6 * t_pipe / n_queries,
                    f"pipelined_parity={pipe_parity} "
                    f"speedup_vs_level={t_part / t_pipe:.2f}x "
                    f"overhead={t_pipe / t_ref:.2f}x",
                )
            )

    # -- hot-beam cache: a hit must be bitwise what a cold run returns -----
    p0 = partitions[0]
    idx = partition_tree(tree, p0)
    ref = jax.block_until_ready(
        tree.infer(xi, xv, beam=beam, topk=topk, method=methods[0])
    )
    cached = ScatterGatherPlanner(
        idx, beam=beam, topk=topk, method=methods[0], sync="pipelined",
        cache_entries=256,
    )
    cold = _bitwise(jax.block_until_ready(cached.infer(xi, xv)), ref)
    hot = _bitwise(jax.block_until_ready(cached.infer(xi, xv)), ref)
    stats = cached.cache_stats()
    t_hot = time_fn(lambda: cached.infer(xi, xv))
    lines.append(
        csv_line(
            f"{shape.name}/pipelined/P{p0}-hot-beam-cache",
            1e6 * t_hot / n_queries,
            f"cache_parity={cold and hot} "
            f"hit_rate={stats['hit_rate']:.2f} entries={stats['entries']}",
        )
    )
    return lines


def run_multidevice(*, n_queries: int = 32, max_labels: int = 4096,
                    seed: int = 0) -> List[str]:
    """P=2 x shards=2 through the MicroBatcher on 4 (forced) host devices."""
    from repro.serving import (
        BatchPolicy, MicroBatcher, ServeConfig, XMRServingEngine,
    )

    n_dev = len(jax.devices())
    if n_dev < 4:
        raise SystemExit(
            f"--multidevice needs 4 devices, found {n_dev} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)"
        )
    shape, tree, rng = _build(max_labels, seed)
    queries = benchmark_queries(shape, n_queries, rng)

    ref_engine = XMRServingEngine(tree, ServeConfig(max_batch=64))
    ref_s, ref_l = ref_engine.serve_batch(queries)

    lines = []
    for sync, suffix, beam_cache in (
        ("level", "", 0),
        ("pipelined", "-pipelined", 64),
    ):
        engine = XMRServingEngine(
            tree, ServeConfig(
                max_batch=64, partitions=2, shards=2,
                partition_sync=sync, beam_cache=beam_cache,
            )
        )
        t0 = time.perf_counter()
        with MicroBatcher(
            engine, BatchPolicy(max_batch=16, max_wait_ms=2.0)
        ) as mb:
            res = [f.result(timeout=300) for f in mb.submit_csr(queries)]
        wall = time.perf_counter() - t0
        s = np.stack([r[0] for r in res])
        l = np.stack([r[1] for r in res])
        parity = bool(np.array_equal(s, ref_s) and np.array_equal(l, ref_l))
        summ = mb.metrics.summary()
        occ = summ.get("partition_occupancy", [])
        mesh = dict(engine.mesh.shape)
        extra = ""
        if sync == "pipelined":
            cache = summ.get("beam_cache", {})
            extra = (
                f" stall_ms={summ.get('pipeline_stall_avg_ms', 0.0):.2f}"
                f" cache_hit_rate={cache.get('hit_rate', 0.0):.2f}"
            )
        lines.append(
            csv_line(
                f"{shape.name}/partitioned/multidevice-P2xS2{suffix}",
                1e6 * wall / n_queries,
                f"partition_parity={parity} "
                f"mesh={mesh['data']}x{mesh['model']} "
                f"occupancy={'/'.join(f'{o:.2f}' for o in occ)} "
                f"devices={n_dev}" + extra,
            )
        )

    # -- overlap gate: with partitions on their own devices, taking the
    # exchange off the matmul's critical path must not cost throughput.
    # Forced host devices only execute *concurrently* when executables are
    # single-threaded (otherwise they contend for one Eigen pool and
    # serialize) — CI sets ``--xla_cpu_multi_thread_eigen=false
    # intra_op_parallelism_threads=1`` on this step; the ``eigen_mt`` field
    # flags runs where the claim is physically unmeasurable. The workload
    # is floored at 64 queries so per-level compute dominates the cheap
    # speculative selects being overlapped.
    import jax.numpy as jnp

    single_thread = "multi_thread_eigen=false" in os.environ.get(
        "XLA_FLAGS", ""
    )
    n_overlap = max(n_queries, 64)
    q_overlap = benchmark_queries(shape, n_overlap, rng)
    xi, xv = map(jnp.asarray, q_overlap.to_ell(256))
    idx = partition_tree(tree, 2)
    pm = place(idx, shards=1)
    level_pl = ScatterGatherPlanner(idx, placement=pm)
    pipe_pl = ScatterGatherPlanner(idx, placement=pm, sync="pipelined")
    # Best-of-3 of median-of-5 per mode: shared 2-core runners are noisy
    # and this is a hard structural gate, not a trend row.
    t_level = min(time_fn(lambda: level_pl.infer(xi, xv)) for _ in range(3))
    t_pipe = min(time_fn(lambda: pipe_pl.infer(xi, xv)) for _ in range(3))
    speedup = t_level / t_pipe
    # Gate only where the claim is measurable: with a shared multi-threaded
    # Eigen pool the forced host devices serialize, so a local run without
    # the flags reports the ratio but cannot honestly fail the flag (CI
    # always sets the flags; eigen_mt in the row keeps it auditable).
    ok = (not single_thread) or t_pipe <= t_level * OVERLAP_TOLERANCE
    lines.append(
        csv_line(
            f"{shape.name}/partitioned/multidevice-overlap",
            1e6 * t_pipe / n_overlap,
            f"overlap_speedup={ok} speedup={speedup:.2f}x "
            f"level_us={1e6 * t_level / n_overlap:.0f} "
            f"columns={pm.n_model} eigen_mt={not single_thread}",
        )
    )
    return lines


def main(argv=None) -> List[str]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--max-labels", type=int, default=4096)
    ap.add_argument("--partitions", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--beam", type=int, default=10)
    ap.add_argument("--multidevice", action="store_true",
                    help="P=2 x shards=2 MicroBatcher smoke on 4 forced "
                         "host devices instead of the single-device panel")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    args = ap.parse_args(argv)
    if args.multidevice:
        lines = run_multidevice(n_queries=args.n, max_labels=args.max_labels)
    else:
        lines = run(
            n_queries=args.n, max_labels=args.max_labels,
            partitions=tuple(args.partitions), beam=args.beam,
        )
    for line in lines:
        print(line)
    if args.json:
        from benchmarks.run import _parse_rows

        with open(args.json, "w") as f:
            json.dump(
                {"rows": _parse_rows(lines), "completed": True}, f, indent=2
            )
        print(f"# wrote {args.json}", file=sys.stderr)
    return lines


if __name__ == "__main__":
    main()
