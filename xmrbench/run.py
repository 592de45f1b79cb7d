"""Run one cell of the benchmark on the chip.

    python3 xmrbench/run.py --workload amazon-670k.batch --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout. Exits nonzero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics and a ``breakdown``), ``device`` and,
last, ``checks``: each number compared with the reference beside its
limit. The same checks are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# libtpu would otherwise write its logs to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from xmrbench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as exc:
        print(f"xmrbench: {exc}", file=sys.stderr)
        return 2
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
