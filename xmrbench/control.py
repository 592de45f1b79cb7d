"""Readings that the limits of ``correct`` are set from, and the control.

    python3 xmrbench/control.py --workload amazon-670k.batch \
        --seeds 101-112 --control-seeds 201-203 --seconds 3

For each ``--seeds`` seed: one whole run of the cell (the program's timed
path at the cell's own load, for ``--seconds``), its readings printed.
For each ``--control-seeds`` seed: the control in the program's place: the
reference computed one precision step below the configuration's (bf16_3x
for f32 at HIGHEST; ``references/xmr_tree.py``) on a sample drawn as a
run draws it, compared with the reference by the same comparison. All in
one process, so the program's compiles are shared. Not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xmrbench import check, generator, registry, weights  # noqa: E402
from xmrbench.harness import checkout_root, seed_streams  # noqa: E402
from xmrbench.references.xmr_tree import TreeReference  # noqa: E402


def control_readings(workload: str, seed: int, *, root: str | None = None,
                     precision: str = "bf16_3x") -> dict:
    """The comparison's readings with the reference at ``precision`` put
    in the program's place, on the sample a run with ``seed`` draws."""
    root = root or checkout_root()
    cell = registry.cell(root, workload)
    cfg = cell.config
    seeds = seed_streams(seed)
    levels = weights.tree_weights(
        seeds["weights"], cfg["d"], cfg["n_labels"], cfg["branching"],
        cfg["col_nnz"], cfg["sibling_overlap"])
    pool = int(cell.mix["pool"])
    qi, qx, qv = generator.label_path_queries(
        np.random.default_rng(seeds["queries"]), levels, cfg["branching"],
        pool, cfg["query_nnz"], cell.mix["queries"])
    rows = np.sort(np.random.default_rng(seeds["sample"]).choice(
        pool, size=min(int(cell.workload["sample"]), pool), replace=False))
    ref = TreeReference(levels, cfg["branching"])
    kw = dict(beam=cfg["beam"], topk=cfg["topk"])
    want = ref.search(qi, qx, qv, rows, **kw)
    got = ref.search(qi, qx, qv, rows, precision=precision, **kw)
    readings = check.compare(got.scores[:, : cfg["topk"]],
                             got.labels[:, : cfg["topk"]], want, cfg["topk"])
    readings["correct"] = check.verdict(readings, cfg["limits"])
    return readings


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part[1:]:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    from xmrbench import harness

    found = {"workload": args.workload, "program": {}, "control": {}}
    for seed in _seeds(args.control_seeds):
        t = time.perf_counter()
        r = control_readings(args.workload, seed)
        found["control"][seed] = r
        print(f"control seed={seed} " + " ".join(
            f"{k}={v!r}" for k, v in r.items())
            + f" in {time.perf_counter() - t:.1f}s", flush=True)
    for seed in _seeds(args.seeds):
        out = harness.run(args.workload, seed, args.seconds, False)
        r = {k: v["value"] for k, v in out["checks"].items()}
        r["correct"] = out["correct"]
        r["metrics"] = {k: v["value"] for k, v in out["metrics"].items()}
        found["program"][seed] = r
        print(f"program seed={seed} " + json.dumps(r), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
