"""Paper Figure 5: MSCM vs a NapkinXC-style reference implementation.

NapkinXC's online inference does a hash-map lookup *per column* (paper §4
item 3: "implemented on a per-column basis"). The TPU analogue of per-column
random access is our vanilla per-column searchsorted baseline; the MSCM side
is the chunked dense-lookup path. The figure's claim — one
traversal per chunk beats one per column by ~an order of magnitude at larger
branching — is what this benchmark checks.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.common import build_benchmark_tree, csv_line, ell_queries, time_fn
from repro.data.xmr_data import PAPER_SHAPES, scaled_shape


def run(datasets=("eurlex-4k", "wiki10-31k"), *, branching=32,
        max_labels=65_536, n=16, seed=0) -> List[str]:
    lines = []
    for ds in datasets:
        shape = PAPER_SHAPES[ds]
        if shape.L > max_labels:
            shape = scaled_shape(shape, max_labels / shape.L)
        rng = np.random.default_rng(seed)
        tree = build_benchmark_tree(shape, branching, rng)
        xi, xv = ell_queries(shape, 1, rng, width=256)
        t_ref = time_fn(lambda: tree.infer(xi, xv, beam=10, topk=10,
                                           method="vanilla"), iters=n)
        t_mscm = time_fn(lambda: tree.infer(xi, xv, beam=10, topk=10,
                                            method="mscm_dense"), iters=n)
        lines.append(csv_line(f"napkin/{ds}/per_column_ref", 1e6 * t_ref, "online"))
        lines.append(csv_line(f"napkin/{ds}/mscm", 1e6 * t_mscm,
                              f"gain={t_ref / t_mscm:.2f}x"))
    return lines


def main(argv=None) -> List[str]:
    lines = run()
    for l in lines:
        print(l)
    return lines


if __name__ == "__main__":
    main()
