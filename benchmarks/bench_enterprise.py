"""Paper Table 4 / §6: enterprise-scale semantic product search.

The paper's model: L = 100M products, d = 4M features, branching 32,
beam 10/20, single-thread batch mode -> 0.88 ms/query (MSCM binary search),
8x over vanilla. 100M labels do not fit one CPU host; we run the same tree
GEOMETRY at L = 32^4 = 1,048,576 (depth matches the paper's lower levels,
d is the full 4M) and report the dense-lookup MSCM-vs-vanilla ratio plus
per-query latency.
"""

from __future__ import annotations

import argparse
import time
from typing import List

import numpy as np

from benchmarks.common import build_benchmark_tree, csv_line, ell_queries, time_fn
from repro.data.xmr_data import ENTERPRISE_SHAPE, XMRShape

SCALED = XMRShape("enterprise-1m", 4_000_000, 32**4, 10_000,
                  ENTERPRISE_SHAPE.query_nnz, ENTERPRISE_SHAPE.col_nnz)


def run(*, beams=(10, 20), n_queries=64, seed=0, branching=32) -> List[str]:
    rng = np.random.default_rng(seed)
    t0 = time.time()
    tree = build_benchmark_tree(SCALED, branching, rng)
    build_s = time.time() - t0
    lines = [csv_line("enterprise/build", 1e6 * build_s,
                      f"L={SCALED.L},d={SCALED.d},mem={tree.memory_bytes()/1e9:.2f}GB")]
    xi, xv = ell_queries(SCALED, n_queries, rng, width=256)
    for beam in beams:
        per_q = {}
        for method in ("mscm_dense", "vanilla"):
            times = []
            for _ in range(3):
                t = time_fn(lambda: tree.infer(xi, xv, beam=beam, topk=10,
                                               method=method), warmup=1, iters=3)
                times.append(1e6 * t / n_queries)
            arr = np.asarray(times)
            per_q[method] = float(np.mean(arr))
            lines.append(csv_line(
                f"enterprise/beam{beam}/{method}", float(np.mean(arr)),
                f"p95={np.percentile(arr, 95):.0f}us",
            ))
        sp = per_q["vanilla"] / per_q["mscm_dense"]
        lines.append(csv_line(f"enterprise/beam{beam}/speedup", 0.0,
                              f"mscm_dense_vs_vanilla={sp:.2f}x"))
    return lines


def main(argv=None) -> List[str]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--beams", nargs="*", type=int, default=[10, 20])
    args = ap.parse_args(argv)
    lines = run(beams=tuple(args.beams), n_queries=args.n_queries)
    for l in lines:
        print(l)
    return lines


if __name__ == "__main__":
    main()
