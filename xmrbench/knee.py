"""Sweep of offered rates for an online cell, to find its knee once.

    python3 xmrbench/knee.py --workload amazon-670k.online --seed 5 \
        --seconds 10 --rates 1000,2000,3000,4000

Builds the cell once, then runs its open-loop window at each rate in turn.
The knee is the highest rate at which the backlog (requests sent and not
yet answered) at the end of the window is no larger than after its first
second. Prints one line per rate; the cell's ``rate_qps`` is then set to
0.8 times the knee by hand. Not part of a run.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xmrbench import generator, harness, registry  # noqa: E402


def backlog_at(t: float, sent: np.ndarray, done: np.ndarray) -> int:
    return int(np.sum((sent <= t) & ~(done <= t)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    root = harness.checkout_root()
    import jax

    cell = registry.cell(root, args.workload)
    harness.require_chips(jax, cell.chips)
    harness.enable_compile_cache(jax, root)
    prog = harness.import_program(root)
    seeds = harness.seed_streams(args.seed)
    levels, tree, queries = harness.build(prog, cell, seeds, {})
    engine = harness.make_engine(prog, cell, tree)
    serve = cell.workload["serve"]
    mb = prog["MicroBatcher"](engine, prog["BatchPolicy"](
        max_batch=serve["max_batch"], max_wait_ms=serve["max_wait_ms"]))
    mb.start()
    rows = [queries.row(i) for i in range(queries.shape[0])]
    rng = np.random.default_rng(seeds["arrivals"])
    span = (lambda name: contextlib.nullcontext())
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        offsets = generator.arrival_offsets(rng, cell.mix["arrivals"], rate,
                                            args.seconds)
        n0 = len(mb.metrics.batch_sizes)
        win = harness.online_window(mb, rows, offsets, args.seconds, span)
        sent = win["due"] + win["lateness"]
        lat = (win["done"] - win["due"]) * 1e3
        b1 = backlog_at(win["t0"] + 1.0, sent, win["done"])
        b_end = backlog_at(win["t1"], sent, win["done"])
        sizes = np.asarray(mb.metrics.batch_sizes[n0:])
        held = b_end <= b1
        if held:
            knee = rate
        print(f"rate={rate:.0f} sent={len(offsets)} backlog_1s={b1} "
              f"backlog_end={b_end} held={held} "
              f"p50_ms={np.nanpercentile(lat, 50):.4f} "
              f"p99_ms={np.nanpercentile(lat, 99):.4f} "
              f"mean_batch={sizes.mean():.3f} "
              f"late_p99_ms={1e3 * np.percentile(win['lateness'], 99):.4f}",
              flush=True)
        time.sleep(1.0)
    mb.stop()
    print(f"knee={knee}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
