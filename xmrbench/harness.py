"""One run of one cell: build from the seed, warm up, measure, check, report.

Order: the chip check, the compile cache, the configuration built from the
seed (weights, the program's tree, the query pool), the cell's own jit
buckets warmed, the measured window, the peak device memory, the check of
what the window served against the plain reference, and the one JSON line.
With ``trace`` on, the window runs under the profiler and the line carries
the per-layer metrics and a breakdown in place of the end-to-end metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from xmrbench import check, generator, registry, weights
from xmrbench import trace as trace_lib
from xmrbench.work import mscm_grouped as work_fns

#: How long past the window's close a late answer is waited for.
LATE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def require_chips(jax, chips: int, allow_cpu: bool = False) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX's backend is {devices[0].platform}; "
                     f"this benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices


def enable_compile_cache(jax, root: str) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed directory ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def import_program(root: str):
    """The program under test, from this checkout's ``src`` only."""
    src = os.path.realpath(os.path.join(root, "src"))
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"no program at {src}/repro")
    sys.path.insert(0, src)
    import repro

    if not os.path.realpath(repro.__file__).startswith(src + os.sep):
        raise ImportError(f"repro imported from {repro.__file__}, not {src}")
    from repro.core import XMRTree
    from repro.serving import (BatchPolicy, MicroBatcher, ServeConfig,
                               XMRServingEngine)
    from repro.sparse.csr import CSC, CSR

    return dict(XMRTree=XMRTree, BatchPolicy=BatchPolicy,
                MicroBatcher=MicroBatcher, ServeConfig=ServeConfig,
                XMRServingEngine=XMRServingEngine, CSC=CSC, CSR=CSR)


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    cell: registry.Cell
    setup_s: float = 0.0
    window_s: float = 0.0
    queries: int = 0                 # answered in the window
    dispatches: int = 0              # engine dispatches (batch cells)
    latencies_ms: Optional[np.ndarray] = None   # per request (online)
    batcher: Optional[Dict[str, np.ndarray]] = None
    trace: Optional[trace_lib.Trace] = None
    chips: int = 1
    work_fn: Optional[Callable[[], dict]] = None
    _work: Optional[dict] = None

    def work(self) -> Optional[dict]:
        """The window's operations and bytes (batch cells), computed once."""
        if self._work is None and self.work_fn is not None:
            self._work = self.work_fn()
        return self._work

    def device_events(self) -> List[trace_lib.Event]:
        return [ev for dev in sorted(self.trace.ops)[: self.chips]
                for ev in self.trace.ops[dev]]

    def module_events(self) -> List[trace_lib.Event]:
        return [ev for dev in sorted(self.trace.modules)[: self.chips]
                for ev in self.trace.modules[dev]]


def _stamp(done: np.ndarray, i: int, _future) -> None:
    done[i] = time.perf_counter()


def seed_streams(seed: int) -> Dict[str, np.random.SeedSequence]:
    entropy = seed if seed >= 0 else (1 << 64) + seed
    names = ("weights", "queries", "arrivals", "sample")
    return dict(zip(names, np.random.SeedSequence(entropy).spawn(len(names))))


def build(prog: dict, cell: registry.Cell, seeds: dict, split: dict):
    """Weights, the program's tree and the query pool, from the seed."""
    cfg = cell.config
    t = time.perf_counter()
    levels = weights.tree_weights(
        seeds["weights"], cfg["d"], cfg["n_labels"], cfg["branching"],
        cfg["col_nnz"], cfg["sibling_overlap"])
    sizes = [lv[3][1] for lv in levels]
    if sizes != cfg["level_sizes"]:
        raise ValueError(f"level sizes {sizes} != {cfg['level_sizes']}")
    split["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = int(cell.mix["pool"])
    qi, qx, qv = generator.label_path_queries(
        np.random.default_rng(seeds["queries"]), levels, cfg["branching"],
        pool, cfg["query_nnz"], cell.mix["queries"])
    queries = prog["CSR"](qi, qx, qv, (pool, cfg["d"]))
    split["queries_s"] = time.perf_counter() - t
    nnz = np.diff(qi)
    print(f"queries: pool={pool} nnz_mean={nnz.mean():.3f} "
          f"nnz_min={nnz.min()} nnz_max={nnz.max()} "
          f"query_nnz={cfg['query_nnz']}")
    t = time.perf_counter()
    tree = prog["XMRTree"].from_weight_matrices(
        [prog["CSC"](ip, ix, dt, sh) for ip, ix, dt, sh in levels],
        cfg["branching"])
    import jax

    jax.block_until_ready(tree.layers)
    split["load_s"] = time.perf_counter() - t
    return levels, tree, queries


def make_engine(prog: dict, cell: registry.Cell, tree):
    cfg, serve = cell.config, cell.workload["serve"]
    return prog["XMRServingEngine"](tree, prog["ServeConfig"](
        method=cfg["method"], beam=cfg["beam"], topk=cfg["topk"],
        score_mode=cfg["score_mode"], max_batch=serve["engine_max_batch"]))


def batch_window(engine, queries, seconds: float, span) -> dict:
    results, ends = [], []
    with span("xmrbench.window"):
        t0 = time.perf_counter()
        while True:
            with span("xmrbench.serve_batch"):
                results.append(engine.serve_batch(queries))
            t1 = time.perf_counter()
            ends.append(t1)
            if t1 - t0 >= seconds:
                break
    return dict(t0=t0, t1=t1, results=results, ends=ends)


def serial_window(engine, singles: list, seconds: float, span) -> dict:
    """Closed loop of one query at a time through ``serve_online``: each
    is sent when the last is answered; its latency is its call's time."""
    results, lat = [], []
    with span("xmrbench.window"):
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with span("xmrbench.serve_online"):
                results.append(engine.serve_online(
                    singles[len(results) % len(singles)]))
            t1 = time.perf_counter()
            lat.append(t1 - t)
            if t1 - t0 >= seconds:
                break
    return dict(t0=t0, t1=t1, results=results, latencies=np.asarray(lat))


def online_window(mb, rows: list, offsets: np.ndarray, seconds: float,
                  span) -> dict:
    n = len(offsets)
    done = np.full(n, np.nan)
    lateness = np.zeros(n)
    futures = []
    with span("xmrbench.window"):
        t0 = time.perf_counter()
        for i in range(n):
            due = t0 + offsets[i]
            wait = due - time.perf_counter()
            if wait > 0:
                with span("xmrbench.await_arrival"):
                    time.sleep(wait)
            lateness[i] = time.perf_counter() - due
            idx, val = rows[i % len(rows)]
            with span("xmrbench.submit"):
                fut = mb.submit(idx, val)
            fut.add_done_callback(functools.partial(_stamp, done, i))
            futures.append(fut)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            with span("xmrbench.await_arrival"):
                time.sleep(rest)
        t1 = time.perf_counter()
    for fut in futures:
        try:
            fut.result(timeout=max(0.0, t1 + LATE_S - time.perf_counter()))
        except Exception:  # noqa: BLE001 - counted below as failed
            pass
    return dict(t0=t0, t1=t1, futures=futures, done=done, lateness=lateness,
                due=t0 + offsets)


@contextlib.contextmanager
def recording_programs(calls: list):
    """Records each call of the program's beam-search jit
    (``core/tree.py::_tree_infer``) made inside the block, and yields that
    jit (None where the program has none). JAX's device memory statistics
    leave out a running program's temporaries, so after the window
    :func:`program_bytes` reads the compiled programs' own analysis."""
    from repro.core import tree as tree_mod

    jitted = getattr(tree_mod, "_tree_infer", None)
    if not hasattr(jitted, "lower"):
        yield None
        return

    def record(*args, **kwargs):
        calls.append((args, kwargs))
        return jitted(*args, **kwargs)

    tree_mod._tree_infer = record
    try:
        yield jitted
    finally:
        tree_mod._tree_infer = jitted


def program_bytes(jitted, calls: list) -> Dict[str, int]:
    """The largest temporaries plus outputs of one recorded program, each
    program compiled again (from the compile cache) for its analysis."""
    import jax

    seen, found = set(), {"programs": 0, "temp_output_bytes": 0}
    for args, kwargs in calls if jitted is not None else []:
        key = str(jax.tree_util.tree_map(
            lambda a: (a.shape, str(a.dtype)) if hasattr(a, "shape") else a,
            (args, kwargs)))
        if key in seen:
            continue
        seen.add(key)
        m = jitted.lower(*args, **kwargs).compile().memory_analysis()
        found["programs"] += 1
        found["temp_output_bytes"] = max(
            found["temp_output_bytes"],
            int(m.temp_size_in_bytes) + int(m.output_size_in_bytes))
    return found


def _count_compiles():
    """A counter of backend compiles (or cache loads) from now on; call
    its ``stop`` to unregister it."""
    import jax

    count = {"n": 0}

    def listener(event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            count["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    count["stop"] = lambda: jax.monitoring.unregister_event_duration_listener(
        listener)
    return count


def _gc_pauses() -> dict:
    """Counts and lengths of the cyclic collector's pauses from now on."""
    found = {"count": 0, "gen2": 0, "max_s": 0.0, "total_s": 0.0, "t": 0.0}

    def callback(phase: str, info: dict) -> None:
        if phase == "start":
            found["t"] = time.perf_counter()
            return
        pause = time.perf_counter() - found["t"]
        found["count"] += 1
        found["gen2"] += info["generation"] == 2
        found["max_s"] = max(found["max_s"], pause)
        found["total_s"] += pause

    gc.callbacks.append(callback)
    found["callback"] = callback
    return found


def _work_fn(levels, visited, branching: int, qnnz: np.ndarray,
             dispatch: int, calls: int, peaks: dict):
    """Operations and bytes of every dispatch the batch window made: each
    call serves the pool in consecutive dispatches of ``dispatch`` rows;
    ``visited`` holds the reference's chunks per level for every row."""
    def least(flops: float, nbytes: float) -> float:
        return work_fns.least_seconds(flops, nbytes, peaks["flops_per_s"],
                                      peaks["bytes_per_s"])

    def compute() -> dict:
        chunk_nnz = [work_fns.chunk_nnz(np.diff(lv[0]), branching)
                     for lv in levels]
        n = len(qnnz)
        kernel_s = step_s = flops = nbytes = 0.0
        chunks = np.zeros(len(levels))
        for lo in range(0, n, dispatch):
            sl = slice(lo, min(n, lo + dispatch))
            q_bytes = work_fns.QUERY_BYTES * float(qnnz[sl].sum())
            step_f, step_b = 0.0, q_bytes     # the step reads queries once
            for li in range(len(levels)):
                w = work_fns.level_work(visited[li][sl], chunk_nnz[li],
                                        qnnz[sl])
                kernel_s += least(w["flops"], w["bytes"])
                flops += w["flops"]
                nbytes += w["bytes"]
                chunks[li] += w["chunks"]
                step_f += w["flops"]
                step_b += w["bytes"] - q_bytes
            step_s += least(step_f, step_b)
        per_call = -(-n // dispatch)
        return dict(kernel_least_s=kernel_s * calls,
                    step_least_s=step_s * calls, flops=flops * calls,
                    bytes=nbytes * calls, dispatches=per_call * calls,
                    chunks_per_dispatch=(chunks / per_call).tolist())
    return compute


def single_rows(prog: dict, queries) -> list:
    """Each query of the pool as a CSR of its own (``serve_online``)."""
    out = []
    for i in range(queries.shape[0]):
        idx, val = queries.row(i)
        out.append(prog["CSR"](np.array([0, len(idx)], np.int64), idx, val,
                               (1, queries.shape[1])))
    return out


def warm(prog: dict, cell: registry.Cell, engine, queries, singles):
    """Compile (or load) and run the cell's own jit buckets once; returns
    the started micro-batcher of a micro-batched cell, else None."""
    cfg, serve = cell.config, cell.workload["serve"]
    arrivals = cell.mix["arrivals"]
    entry = generator.ENTRIES.get(arrivals["kind"])
    if entry != serve["entry"]:
        raise ValueError(f"{arrivals['kind']!r} arrivals are served by "
                         f"{entry!r}, not {serve['entry']!r}")
    if entry == "serve_batch":
        if arrivals["block"] != queries.shape[0]:
            raise ValueError("a serve_batch cell serves its whole pool in "
                             "each call")
        engine.warmup(cfg["d"], batch_sizes=(serve["engine_max_batch"],))
        engine.serve_batch(queries)                      # one whole call
        return None
    if entry == "serve_online":
        engine.warmup(cfg["d"], batch_sizes=(1,))
        engine.serve_online(singles[0])
        return None
    mb = prog["MicroBatcher"](engine, prog["BatchPolicy"](
        max_batch=serve["max_batch"], max_wait_ms=serve["max_wait_ms"]))
    mb.start()                                           # warms 1..max_batch
    for fut in [mb.submit(*queries.row(i)) for i in range(serve["max_batch"])]:
        fut.result(timeout=300)
    return mb


def batch_answers(rec: Run, win: dict, pool: int, dispatch: int,
                  rng: np.random.Generator, n_sample: int):
    """Fill ``rec`` from a batch window; the sampled answers and rows."""
    calls = len(win["results"])
    rec.queries = calls * pool
    rec.dispatches = calls * -(-pool // dispatch)
    pick = np.sort(rng.choice(rec.queries, size=min(n_sample, rec.queries),
                              replace=False))
    got_s = np.stack([win["results"][p // pool][0][p % pool] for p in pick])
    got_l = np.stack([win["results"][p // pool][1][p % pool] for p in pick])
    call_s = np.diff(np.concatenate([[win["t0"]], win["ends"]]))
    print(f"window: calls={calls} queries={rec.queries} "
          f"dispatches={rec.dispatches} window_s={rec.window_s:.6f} "
          f"call_s={np.round(call_s, 4).tolist()}")
    return got_s, got_l, pick % pool, rec.queries, 0


def serial_answers(rec: Run, win: dict, pool: int,
                   rng: np.random.Generator, n_sample: int):
    """Fill ``rec`` from a serial window; the sampled answers and rows."""
    results = win["results"]
    rec.queries = len(results)
    rec.dispatches = len(results)
    rec.latencies_ms = win["latencies"] * 1e3
    pick = np.sort(rng.choice(rec.queries, size=min(n_sample, rec.queries),
                              replace=False))
    got_s = np.stack([results[p][0][0] for p in pick])
    got_l = np.stack([results[p][1][0] for p in pick])
    print(f"window: queries={rec.queries} window_s={rec.window_s:.6f} "
          f"latency_ms p99={np.percentile(rec.latencies_ms, 99):.4f} "
          f"max={rec.latencies_ms.max():.4f}")
    return got_s, got_l, pick % pool, rec.queries, 0


def online_answers(rec: Run, win: dict, mb, n_waits0: int, n_batches0: int,
                   pool: int, rng: np.random.Generator, n_sample: int):
    """Fill ``rec`` from an online window; the sampled answers and rows."""
    futures = win["futures"]
    ok = np.array([f.done() and f.exception() is None for f in futures])
    lat = (win["done"] - win["due"]) * 1e3
    lat[~ok] = np.inf
    rec.latencies_ms = lat
    rec.queries = int(ok.sum())
    m = mb.metrics
    rec.batcher = dict(queue_wait_ms=np.asarray(m.queue_wait_ms[n_waits0:]),
                       batch_sizes=np.asarray(m.batch_sizes[n_batches0:]),
                       bucket_sizes=np.asarray(m.bucket_sizes[n_batches0:]))
    late = win["lateness"] * 1e3
    tail = np.percentile(lat, [95, 99, 99.9])
    print(f"latency_ms p95={tail[0]:.4f} p99={tail[1]:.4f} "
          f"p99.9={tail[2]:.4f} max={lat.max():.4f}")
    print(f"window: requests={len(futures)} answered={rec.queries} "
          f"batches={len(rec.batcher['batch_sizes'])} "
          f"generator_late_ms p50={np.percentile(late, 50):.4f} "
          f"p99={np.percentile(late, 99):.4f} max={late.max():.4f} "
          f"max_at_s={win['due'][int(np.argmax(late))] - win['t0']:.3f} "
          f"over_100ms={int(np.sum(late > 100))}")
    pick = np.sort(rng.choice(np.flatnonzero(ok),
                              size=min(n_sample, rec.queries), replace=False))
    answers = [futures[i].result() for i in pick]
    got_s = np.stack([a[0] for a in answers])
    got_l = np.stack([a[1] for a in answers])
    return got_s, got_l, pick % pool, len(futures), int((~ok).sum())


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Optional[str] = None, t_start: Optional[float] = None,
        allow_cpu: bool = False, cache: bool = True,
        serve_hook: Optional[Callable] = None) -> dict:
    """One run; returns the result object that ``run.py`` prints last.

    ``allow_cpu`` and ``serve_hook`` (applied to the engine before the
    window, to plant a fault) exist for the benchmark's own tests.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    root = root or checkout_root()
    import jax

    cell = registry.cell(root, workload)
    devices = require_chips(jax, cell.chips, allow_cpu)
    if cache:
        print(f"compile cache: {enable_compile_cache(jax, root)}")
    readers = registry.readers(root, cell)
    prog = import_program(root)
    cfg, wl, serve = cell.config, cell.workload, cell.workload["serve"]
    kind = devices[0].device_kind
    with open(os.path.join(root, "xmrbench", "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if trace and peaks is None:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    seeds = seed_streams(seed)

    # Set-up: everything up to the first timed request.
    split: Dict[str, float] = {}
    levels, tree, queries = build(prog, cell, seeds, split)
    engine = make_engine(prog, cell, tree)
    if not allow_cpu and engine.method != cfg["served_method"]:
        raise RuntimeError(f"method {cfg['method']!r} resolved to "
                           f"{engine.method!r}, not {cfg['served_method']!r}")
    if serve_hook is not None:
        serve_hook(engine)
    entry = serve["entry"]
    singles = single_rows(prog, queries) if entry == "serve_online" else None
    t = time.perf_counter()
    calls: list = []
    with recording_programs(calls) as jitted:
        mb = warm(prog, cell, engine, queries, singles)
    split["warm_s"] = time.perf_counter() - t
    if mb is not None:
        rows = [queries.row(i) for i in range(queries.shape[0])]
        offsets = generator.arrival_offsets(
            np.random.default_rng(seeds["arrivals"]), cell.mix["arrivals"],
            wl["rate_qps"], seconds)
    setup_s = time.perf_counter() - t_start
    print("setup: " + " ".join(f"{k}={v:.3f}" for k, v in split.items())
          + f" setup_s={setup_s:.3f}")

    # The measured window, under the profiler with --trace 1.
    trace_dir = os.path.join(root, ".xmrbench_trace", f"{workload}-{seed}")
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))
    compiles = _count_compiles()
    # The set-up's objects (JAX's, the generated data's) are never freed;
    # left to the cyclic collector, each full collection in the window
    # would scan them again and stall every thread of the server.
    gc.collect()
    gc.freeze()
    pauses = _gc_pauses()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if entry == "serve_batch":
        win = batch_window(engine, queries, seconds, span)
    elif entry == "serve_online":
        win = serial_window(engine, singles, seconds, span)
    else:
        n_batches0 = len(mb.metrics.batch_sizes)
        n_waits0 = len(mb.metrics.queue_wait_ms)
        win = online_window(mb, rows, offsets, seconds, span)
    if trace:
        jax.profiler.stop_trace()
    gc.callbacks.remove(pauses["callback"])
    gc.unfreeze()
    compiles["stop"]()
    window_compiles = compiles["n"]
    stats = [d.memory_stats() or {} for d in devices[: cell.chips]]
    stats_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    memory_in_use = max(int(s.get("bytes_in_use", 0)) for s in stats)
    programs = program_bytes(jitted, calls)
    del calls
    # What stays in use (the tree) plus the largest program's temporaries
    # and outputs, where that is more than the allocator's own peak.
    memory_peak = max(stats_peak,
                      memory_in_use + programs["temp_output_bytes"])

    # What the window served, and a sample of it drawn from the seed.
    rec = Run(cell=cell, setup_s=setup_s, window_s=win["t1"] - win["t0"],
              chips=cell.chips)
    rng = np.random.default_rng(seeds["sample"])
    pool = queries.shape[0]
    if entry == "serve_batch":
        got_s, got_l, rows_of_sample, attempted, failed = batch_answers(
            rec, win, pool, serve["engine_max_batch"], rng, int(wl["sample"]))
    elif entry == "serve_online":
        got_s, got_l, rows_of_sample, attempted, failed = serial_answers(
            rec, win, pool, rng, int(wl["sample"]))
    else:
        mb.stop()
        got_s, got_l, rows_of_sample, attempted, failed = online_answers(
            rec, win, mb, n_waits0, n_batches0, pool, rng, int(wl["sample"]))
    print(f"device memory: memory_peak_bytes={memory_peak} "
          f"peak_bytes_in_use={stats_peak} bytes_in_use={memory_in_use} "
          f"program_temp_output_bytes={programs['temp_output_bytes']} "
          f"programs={programs['programs']} "
          f"compiles_in_window={window_compiles}")
    print(f"gc in window: collections={pauses['count']} "
          f"gen2={pauses['gen2']} max_pause_ms={1e3 * pauses['max_s']:.3f} "
          f"total_pause_ms={1e3 * pauses['total_s']:.3f}")
    del engine, tree, mb, win, singles
    gc.collect()

    # The check against the plain reference, with the program's state freed.
    t = time.perf_counter()
    reference = importlib.import_module(
        "xmrbench.references." + cfg["reference"])
    ref = reference.TreeReference(levels, cfg["branching"])
    need_work = trace and entry == "serve_batch"
    ref_rows = np.arange(pool) if need_work else np.unique(rows_of_sample)
    res = ref.search(queries.indptr, queries.indices, queries.data, ref_rows,
                     beam=cfg["beam"], topk=cfg["topk"])
    at = np.searchsorted(ref_rows, rows_of_sample)
    sub = dataclasses.replace(res, scores=res.scores[at],
                              labels=res.labels[at],
                              ambiguous=res.ambiguous[at], visited=[])
    readings = check.compare(got_s, got_l, sub, cfg["topk"])
    readings["failed"] = float(failed)
    limits = dict(cfg["limits"], failed=0.0)
    correct = check.verdict(readings, limits)
    print(f"reference: rows={len(ref_rows)} sample={len(rows_of_sample)} "
          f"checked={readings['checked']:.0f} "
          f"ambiguous={readings['ambiguous']:.0f} "
          f"in {time.perf_counter() - t:.3f}s")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out: dict = {"correct": bool(correct), "attempted": int(attempted),
                 "failed": int(failed)}
    if trace:
        rec.trace = trace_lib.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if need_work:
            rec.work_fn = _work_fn(levels, res.visited, cfg["branching"],
                                   np.diff(queries.indptr),
                                   serve["engine_max_batch"],
                                   rec.queries // pool, peaks)
        lo, hi = rec.trace.window
        per_chip = [trace_lib.busy_ns(rec.trace.ops[dev], lo, hi)
                    for dev in sorted(rec.trace.ops)[: cell.chips]]
        device["busy_s"] = 1e-9 * sum(per_chip) / max(1, len(per_chip))
        device["window_s"] = rec.trace.window_s
        chosen, kind_readers = cell.per_layer, readers["metrics"]
        events = rec.device_events()
        out["breakdown"] = {
            "device_ops": [[trace_lib.short_name(n), v] for n, v in
                           trace_lib.top_ops(trace_lib.clip(events, lo, hi))],
            "idle_gaps": [list(x) for x in trace_lib.idle_gaps(
                events, rec.trace.host, lo, hi)],
        }
    else:
        chosen, kind_readers = cell.end_to_end, readers["e2e"]
    metrics: Dict[str, dict] = {}
    for m in chosen:
        value = kind_readers[m["name"]](rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if rec.work() is not None:
        print("work: " + " ".join(f"{k}={v}" for k, v in rec.work().items()))
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": readings[k], "limit": limits[k]}
                     for k in limits}
    return out


def report(out: dict) -> None:
    """Checks as the last lines of stderr; the result as stdout's last."""
    sys.stdout.flush()
    for k, v in out["checks"].items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    sys.stdout.flush()
