"""The program's own spans in one traced window of a cell: host time per
dispatch, each dispatch's time in flight, and the device's idle gaps named
by what the server's threads were doing.

    python3 xmrbench/program_spans.py --workload amazon-670k.online \
        --seed 5 --seconds 51

Builds and warms the cell as a run does and serves one window under the
profiler, as ``--trace 1`` does. From the trace it reads the serving
path's ``repro.*`` spans (``src/repro/serving/spans.py``), which
``trace.load`` leaves out, and prints one JSON line (see :func:`measure`).
A program without those spans reads no dispatches. Not part of a run.
"""

from __future__ import annotations

import argparse
import bisect
import gc
import glob
import json
import os
import shutil
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xmrbench import generator, harness, registry  # noqa: E402
from xmrbench import trace as trace_lib  # noqa: E402

PREFIX = "repro."
MARSHAL = "repro.engine.marshal"
DISPATCH = "repro.engine.dispatch"
WAIT = "repro.engine.wait"
FETCH = "repro.engine.fetch"
FORM = "repro.batcher.form"
BATCH_DISPATCH = "repro.batcher.dispatch"
RESOLVE = "repro.batcher.resolve"
#: The spans every dispatch has; a micro-batched one has BATCHER's too.
ENGINE = (MARSHAL, DISPATCH, WAIT, FETCH)
BATCHER = (FORM, BATCH_DISPATCH, RESOLVE)


class Span(NamedTuple):
    name: str
    start: float          # ns, the profiler's clock
    end: float
    thread: str           # host line as "<line name>/<position in plane>"
    stats: dict


def load(trace_dir: str) -> List[Span]:
    """The ``repro.*`` and ``xmrbench.*`` host spans of the newest
    ``.xplane.pb`` under ``trace_dir``, with their thread and stats."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith((PREFIX, trace_lib.HOST_PREFIX)):
                    out.append(Span(ev.name, float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns),
                                    f"{line.name}/{i}", dict(ev.stats)))
    return out


def started(spans: Sequence[Span], name: str, lo: float,
            hi: float) -> List[Span]:
    return [s for s in spans if s.name == name and lo <= s.start < hi]


def _events(spans: Sequence[Span]) -> List[trace_lib.Event]:
    return [(s.name, s.start, s.end) for s in spans]


def missing(spans: Sequence[Span], names: Sequence[str], lo: float,
            hi: float) -> Dict[str, int]:
    """Per span name, the dispatches started in ``[lo, hi)`` that lack it."""
    ids = {s.stats["dispatch"] for s in started(spans, DISPATCH, lo, hi)}
    return {n: len(ids - {s.stats["dispatch"] for s in spans if s.name == n})
            for n in names}


def host_ms_per_dispatch(spans: Sequence[Span], lo: float,
                         hi: float) -> Optional[float]:
    """Host work of the dispatching thread per dispatch: the union of its
    ``repro.*`` spans in ``[lo, hi]`` less the time under
    ``repro.engine.wait`` and ``repro.batcher.form``, over the dispatches
    started in the window."""
    enq = started(spans, DISPATCH, lo, hi)
    if not enq:
        return None
    threads = {s.thread for s in enq}
    mine = [s for s in spans if s.thread in threads
            and s.name.startswith(PREFIX)]
    blocked = [s for s in mine if s.name in (WAIT, FORM)]
    ns = (trace_lib.busy_ns(_events(mine), lo, hi)
          - trace_lib.busy_ns(_events(blocked), lo, hi))
    return ns * 1e-6 / len(enq)


def inflight_ms(spans: Sequence[Span], lo: float, hi: float) -> List[float]:
    """Per dispatch started in the window, from the end of its enqueue
    (``repro.engine.dispatch``) to the end of ``repro.engine.wait`` with
    the same ``dispatch`` id."""
    waits = {s.stats["dispatch"]: s.end for s in spans if s.name == WAIT}
    return [(waits[s.stats["dispatch"]] - s.end) * 1e-6
            for s in started(spans, DISPATCH, lo, hi)
            if s.stats["dispatch"] in waits]


def device_split_ms(spans: Sequence[Span], modules: Sequence[trace_lib.Event],
                    lo: float, hi: float) -> Dict[str, List[float]]:
    """Each dispatch's time in flight in three parts: ``queued`` from the
    end of its enqueue to its program's start on the device, ``device``
    the program's execution, ``notice`` from its end to the end of the
    wait. A dispatch's program is the last execution of the beam-search
    program to end before its wait ends; a dispatch whose program so found
    started before the dispatch did (its own was not recorded) is left
    out."""
    runs = sorted((ev for ev in modules if "_tree_infer" in ev[0]),
                  key=lambda ev: ev[2])
    ends = [ev[2] for ev in runs]
    waits = {s.stats["dispatch"]: s.end for s in spans if s.name == WAIT}
    out: Dict[str, List[float]] = {"queued": [], "device": [], "notice": []}
    for s in started(spans, DISPATCH, lo, hi):
        done = waits.get(s.stats["dispatch"])
        i = bisect.bisect_right(ends, done) - 1 if done is not None else -1
        if i < 0 or runs[i][1] < s.start:
            continue
        _, run_start, run_end = runs[i]
        out["queued"].append((run_start - s.end) * 1e-6)
        out["device"].append((run_end - run_start) * 1e-6)
        out["notice"].append((done - run_end) * 1e-6)
    return out


def longest_gaps(events: Sequence[trace_lib.Event], lo: float, hi: float,
                 k: int = 10) -> List[Tuple[float, float]]:
    """The ``k`` longest device-idle intervals in ``[lo, hi]``."""
    edges = [lo] + [x for iv in trace_lib.union(trace_lib.clip(events, lo, hi))
                    for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return sorted(gaps, key=lambda g: g[0] - g[1])[:k]


def innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    """The latest-started span open at ``t``, the window span left out."""
    open_ = [s for s in spans if s.start <= t < s.end
             and s.name != trace_lib.WINDOW_SPAN]
    return max(open_, key=lambda s: s.start) if open_ else None


def label_gaps(events: Sequence[trace_lib.Event], spans: Sequence[Span],
               lo: float, hi: float, k: int = 10) -> List[dict]:
    """The ``k`` longest device-idle gaps, each named by the innermost
    ``repro.*`` span open at its midpoint on any thread, else by the
    innermost ``xmrbench.*`` span, else ``idle``; with the innermost span
    open on each thread."""
    program = [s for s in spans if s.name.startswith(PREFIX)]
    by_thread: Dict[str, List[Span]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    out = []
    for s, e in longest_gaps(events, lo, hi, k):
        mid = 0.5 * (s + e)
        label = innermost(program, mid) or innermost(spans, mid)
        on = {th: innermost(mine, mid) for th, mine in by_thread.items()}
        out.append({"ms": (e - s) * 1e-6,
                    "label": label.name if label else "idle",
                    "threads": {th: sp.name for th, sp in sorted(on.items())
                                if sp}})
    return out


def _p50(values: Sequence[float]) -> Optional[float]:
    return float(np.median(values)) if len(values) else None


def _durations(spans: Sequence[Span], name: str, lo: float,
               hi: float) -> List[float]:
    return [(s.end - s.start) * 1e-6 for s in started(spans, name, lo, hi)]


def measure(workload: str, seed: int, seconds: float, *,
            root: Optional[str] = None, allow_cpu: bool = False) -> dict:
    """One traced window of ``workload``; the spans' readings.

    ``dispatches``: dispatches started in the window; ``spans``: spans of
    each name started there; ``missing``: started dispatches lacking each
    span; ``host_ms_per_dispatch``; ``inflight_ms`` (p50, p90, max);
    ``split_ms``: the p50 of each part of :func:`device_split_ms`;
    ``p50_ms``: the p50 duration of each span; ``window``: what the
    window served (queries/s, or latency and queue wait p50, traced);
    ``gaps``: :func:`label_gaps`.
    """
    root = root or harness.checkout_root()
    import jax

    cell = registry.cell(root, workload)
    devices = harness.require_chips(jax, cell.chips, allow_cpu)
    prog = harness.import_program(root)
    seeds = harness.seed_streams(seed)
    levels, tree, queries = harness.build(prog, cell, seeds, {})
    engine = harness.make_engine(prog, cell, tree)
    entry = cell.workload["serve"]["entry"]
    singles = (harness.single_rows(prog, queries)
               if entry == "serve_online" else None)
    mb = harness.warm(prog, cell, engine, queries, singles)
    trace_dir = os.path.join(root, ".xmrbench_trace",
                             f"spans-{workload}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    span = jax.profiler.TraceAnnotation
    if mb is not None:
        rows = [queries.row(i) for i in range(queries.shape[0])]
        offsets = generator.arrival_offsets(
            np.random.default_rng(seeds["arrivals"]), cell.mix["arrivals"],
            cell.workload["rate_qps"], seconds)
        n_waits0 = len(mb.metrics.queue_wait_ms)
    gc.collect()
    gc.freeze()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        if entry == "serve_batch":
            win = harness.batch_window(engine, queries, seconds, span)
        elif entry == "serve_online":
            win = harness.serial_window(engine, singles, seconds, span)
        else:
            win = harness.online_window(mb, rows, offsets, seconds, span)
            mb.stop()   # every span of the window ends inside the trace
    finally:
        jax.profiler.stop_trace()
        gc.unfreeze()
    tr = trace_lib.load(trace_dir)
    spans = load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    lo, hi = tr.window
    chips = sorted(tr.ops)[: cell.chips]
    events = [ev for dev in chips for ev in tr.ops[dev]]
    modules = [ev for dev in chips for ev in tr.modules[dev]]
    window_s = win["t1"] - win["t0"]
    if entry == "serve_batch":
        served = {"queries_per_s":
                  len(win["results"]) * queries.shape[0] / window_s}
    elif entry == "serve_online":
        served = {"latency_p50_ms": 1e3 * float(np.median(win["latencies"]))}
    else:
        lat = (win["done"] - win["due"]) * 1e3
        served = {"latency_p50_ms": float(np.median(lat)),
                  "queue_wait_p50_ms": float(np.median(
                      mb.metrics.queue_wait_ms[n_waits0:]))}
    names = ENGINE + (BATCHER if mb is not None else ())
    flight = inflight_ms(spans, lo, hi)
    split = device_split_ms(spans, modules, lo, hi)
    return {
        "workload": workload, "seed": seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind},
        "window_s": tr.window_s,
        "dispatches": len(started(spans, DISPATCH, lo, hi)),
        "spans": {n: len(started(spans, n, lo, hi)) for n in names},
        "missing": missing(spans, names, lo, hi),
        "host_ms_per_dispatch": host_ms_per_dispatch(spans, lo, hi),
        "inflight_ms": ({"p50": _p50(flight),
                         "p90": float(np.percentile(flight, 90)),
                         "max": max(flight)} if flight else None),
        "split_ms": {k: _p50(v) for k, v in split.items()},
        "split_dispatches": len(split["device"]),
        "p50_ms": {n: _p50(_durations(spans, n, lo, hi)) for n in names},
        "window": served,
        "gaps": label_gaps(events, spans, lo, hi),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    import jax

    harness.enable_compile_cache(jax, harness.checkout_root())
    print(json.dumps(measure(args.workload, args.seed, args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
