"""Serving subsystem: vectorized marshalling, coalescing triggers, padding.

Pins the properties the async engine must not break:
1. the vectorized CSR→ELL path equals the per-row loop oracle (including a
   truncation-parity property sweep for width < nnz);
2. the RequestQueue fires on exactly the documented triggers
   (size / deadline / close-flush);
3. bucket padding is invisible — micro-batched results are bitwise-identical
   to per-query serving;
4. MicroBatcher.start() pre-warms every jit bucket (no compile in the
   serving path) and a ready batch dispatches before the worker blocks on
   the in-flight one;
5. a dispatch fault fails only its own batch — every future resolves
   exactly once and the queue keeps serving;
6. latency accounting stays honest: amortized batch averages never enter
   the per-query percentile series;
7. the ``queue_depth="auto"`` capacity probe is total — zero/slow drain
   rates and a missing deadline all resolve to a sane bound — and
   ``stop()`` during an in-flight probe waits it out instead of closing
   the queue under it;
8. every dispatch's host spans (``repro.*``, on the profiler's clock) share
   its dispatch id and nest in the order the serving path runs them, and a
   profiler session leaves results bitwise unchanged.
"""

import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import XMRTree
from repro.core.tree import _tree_infer
from repro.serving import (
    AdmissionPolicy,
    BatchPolicy,
    MicroBatcher,
    ServeConfig,
    XMRServingEngine,
)
from repro.serving import spans
from repro.serving.batcher import (
    TRIGGER_DEADLINE,
    TRIGGER_FLUSH,
    TRIGGER_SIZE,
    RequestQueue,
    _InFlight,
    _Request,
)
from repro.sparse import (
    random_sparse_csr,
    rows_to_ell,
    rows_to_ell_loop,
)
from tests.conftest import make_tree_weights

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# 1. vectorized CSR→ELL vs the per-row loop oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [None, 1, 4, 64])
def test_rows_to_ell_matches_loop(rng, width):
    x = random_sparse_csr(40, 300, 12, rng)
    for rows in (
        np.arange(40),
        np.array([0, 39, 7, 7, 20]),   # arbitrary order, duplicates
        np.zeros(0, np.int64),         # empty selection
    ):
        vi, vv = rows_to_ell(x, rows, width)
        li, lv = rows_to_ell_loop(x, rows, width)
        np.testing.assert_array_equal(vi, li)
        np.testing.assert_array_equal(vv, lv)


def test_rows_to_ell_truncation_and_sentinel(rng):
    x = random_sparse_csr(8, 100, 20, rng)
    w = 5
    idx, val = rows_to_ell(x, np.arange(8), w)
    assert idx.shape == (8, w) and val.shape == (8, w)
    for i in range(8):
        ri, rv = x.row(i)
        k = min(len(ri), w)
        np.testing.assert_array_equal(idx[i, :k], ri[:k])
        assert (idx[i, k:] == 100).all() and (val[i, k:] == 0).all()


def test_to_ell_uses_vectorized_path(rng):
    x = random_sparse_csr(25, 200, 10, rng)
    vi, vv = x.to_ell()
    li, lv = rows_to_ell_loop(x, np.arange(25), None)
    np.testing.assert_array_equal(vi, li)
    np.testing.assert_array_equal(vv, lv)


def test_rows_to_ell_empty_rows(rng):
    from repro.sparse.csr import CSR

    x = CSR.from_dense(np.zeros((3, 10), np.float32))
    idx, val = rows_to_ell(x, np.arange(3), 4)
    assert (idx == 10).all() and (val == 0).all()


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(
        n=st.integers(1, 24),
        d=st.integers(4, 300),
        nnz=st.integers(1, 40),
        width=st.integers(1, 12),
        seed=st.integers(0, 2**16),
    )
    def test_rows_to_ell_truncation_parity_property(n, d, nnz, width, seed):
        """Vectorized truncation (width < nnz) matches the per-row loop
        oracle for arbitrary shapes, widths, and row selections."""
        rng = np.random.default_rng(seed)
        x = random_sparse_csr(n, d, min(nnz, d), rng)
        sel = rng.integers(0, n, size=rng.integers(0, 2 * n))  # dups, any order
        vi, vv = rows_to_ell(x, sel, width)
        li, lv = rows_to_ell_loop(x, sel, width)
        np.testing.assert_array_equal(vi, li)
        np.testing.assert_array_equal(vv, lv)
        # truncation semantics: never wider than width, sentinel-padded tails
        assert vi.shape == (len(sel), width)
        tail_mask = vi == d
        assert (vv[tail_mask] == 0).all()
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_rows_to_ell_truncation_parity_property():
        pass


# ---------------------------------------------------------------------------
# 2. RequestQueue coalescing triggers (tested directly, no worker thread)
# ---------------------------------------------------------------------------

def _req(t=None):
    from concurrent.futures import Future

    return _Request(
        idx=np.zeros(1, np.int32),
        val=np.zeros(1, np.float32),
        future=Future(),
        t_enqueue=time.perf_counter() if t is None else t,
    )


def test_size_trigger_fires_immediately():
    q = RequestQueue()
    for _ in range(20):
        q.put(_req())
    t0 = time.perf_counter()
    batch, trigger = q.next_batch(16, max_wait_s=10.0)
    assert trigger == TRIGGER_SIZE
    assert len(batch) == 16
    assert time.perf_counter() - t0 < 1.0  # did not wait for the deadline
    assert len(q) == 4


def test_deadline_trigger_fires_after_wait():
    q = RequestQueue()
    for _ in range(3):
        q.put(_req())
    t0 = time.perf_counter()
    batch, trigger = q.next_batch(16, max_wait_s=0.05)
    waited = time.perf_counter() - t0
    assert trigger == TRIGGER_DEADLINE
    assert len(batch) == 3
    assert waited >= 0.04  # held for the deadline, not a spurious wakeup


def test_close_flushes_partial_batch():
    q = RequestQueue()
    q.put(_req())
    q.close()
    batch, trigger = q.next_batch(16, max_wait_s=60.0)
    assert trigger == TRIGGER_FLUSH and len(batch) == 1
    batch, _ = q.next_batch(16, max_wait_s=60.0)
    assert batch is None  # closed + drained
    with pytest.raises(RuntimeError):
        q.put(_req())


def test_nonblocking_poll_returns_empty():
    q = RequestQueue()
    q.put(_req())  # present but neither trigger fired
    batch, trigger = q.next_batch(16, max_wait_s=60.0, block=False)
    assert batch == [] and trigger == ""


# ---------------------------------------------------------------------------
# 3. end-to-end micro-batching vs per-query serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_setup():
    rng = np.random.default_rng(7)
    d, B = 200, 8
    ws = make_tree_weights(rng, d, [8, 64, 512], B)
    tree = XMRTree.from_weight_matrices(ws, B)
    engine = XMRServingEngine(tree, ServeConfig(ell_width=32, max_batch=64))
    engine.warmup(d, batch_sizes=(1, 2, 4, 8, 16))
    queries = random_sparse_csr(45, d, 15, rng)  # 45: forces a ragged tail
    ref_s, ref_l = engine.serve_online(queries)
    return engine, queries, ref_s, ref_l


def test_microbatch_bitwise_equals_per_query(serving_setup):
    engine, queries, ref_s, ref_l = serving_setup
    mb = MicroBatcher(engine, BatchPolicy(max_batch=16, max_wait_ms=5.0))
    futs = mb.submit_csr(queries)  # enqueue before start: deterministic coalescing
    mb.start()
    res = [f.result(timeout=60) for f in futs]
    mb.stop()
    np.testing.assert_array_equal(np.stack([r[0] for r in res]), ref_s)
    np.testing.assert_array_equal(np.stack([r[1] for r in res]), ref_l)
    s = mb.metrics.summary()
    assert s["count"] == queries.shape[0]
    # 45 requests at max_batch=16 → two size-triggered 16s + a 13 tail
    assert TRIGGER_SIZE in s["triggers"]
    assert max(mb.metrics.batch_sizes) == 16


def test_bucket_padding_invisible(serving_setup):
    """13 requests pad to the 16-bucket; results equal the unpadded run."""
    engine, queries, ref_s, ref_l = serving_setup
    sub = queries.slice_rows(np.arange(13))
    xi, xv = engine.marshal_rows(sub, np.arange(13), bucket=16)
    assert xi.shape[0] == 16
    s, l = engine._run(xi, xv)
    np.testing.assert_array_equal(np.asarray(s)[:13], ref_s[:13])
    np.testing.assert_array_equal(np.asarray(l)[:13], ref_l[:13])
    # padding rows are empty sentinel queries
    assert (np.asarray(xi)[13:] == queries.shape[1]).all()


def test_deadline_batches_resolve_without_size_trigger(serving_setup):
    engine, queries, ref_s, ref_l = serving_setup
    mb = MicroBatcher(engine, BatchPolicy(max_batch=16, max_wait_ms=10.0))
    mb.start()
    futs = mb.submit_csr(queries.slice_rows(np.arange(3)))
    res = [f.result(timeout=60) for f in futs]  # resolves via deadline, not size
    mb.stop()
    np.testing.assert_array_equal(np.stack([r[0] for r in res]), ref_s[:3])
    np.testing.assert_array_equal(np.stack([r[1] for r in res]), ref_l[:3])
    trig = mb.metrics.summary()["triggers"]
    assert TRIGGER_SIZE not in trig
    assert TRIGGER_DEADLINE in trig or TRIGGER_FLUSH in trig


def test_serve_batch_matches_online(serving_setup):
    engine, queries, ref_s, ref_l = serving_setup
    s, l = engine.serve_batch(queries)
    np.testing.assert_array_equal(s, ref_s)
    np.testing.assert_array_equal(l, ref_l)


def test_label_perm_applied_through_batcher(serving_setup):
    engine, queries, ref_s, ref_l = serving_setup
    perm = np.arange(engine.tree.n_labels)[::-1].copy()
    eng2 = XMRServingEngine(engine.tree, engine.config, label_perm=perm)
    with MicroBatcher(eng2, BatchPolicy(max_batch=16, max_wait_ms=5.0)) as mb:
        res = [f.result(timeout=60) for f in mb.submit_csr(queries)]
    np.testing.assert_array_equal(np.stack([r[1] for r in res]), perm[ref_l])


# ---------------------------------------------------------------------------
# 4. start() warmup + dispatch-before-finalize
# ---------------------------------------------------------------------------

def test_start_warms_buckets_no_compile_in_serving_path(serving_setup):
    """start() pre-compiles every bucket; live traffic never hits XLA."""
    engine, queries, ref_s, ref_l = serving_setup
    # distinct ell_width → distinct jit cache entries for this test alone
    eng = XMRServingEngine(engine.tree, ServeConfig(ell_width=48, max_batch=64))
    mb = MicroBatcher(eng, BatchPolicy(max_batch=8, max_wait_ms=2.0))
    before = _tree_infer._cache_size()
    mb.start()
    warmed = _tree_infer._cache_size()
    assert warmed > before  # buckets compiled up front by start()
    futs = mb.submit_csr(queries.slice_rows(np.arange(13)))  # buckets 8 + 8
    for f in futs:
        f.result(timeout=60)
    mb.stop()
    assert _tree_infer._cache_size() == warmed  # no compile after start


def test_warmup_on_start_opt_out(serving_setup):
    engine, *_ = serving_setup
    eng = XMRServingEngine(engine.tree, ServeConfig(ell_width=56, max_batch=64))
    mb = MicroBatcher(eng, BatchPolicy(max_batch=8), warmup_on_start=False)
    before = _tree_infer._cache_size()
    mb.start()
    assert _tree_infer._cache_size() == before  # opted out: nothing compiled
    mb.stop()


def test_ready_batch_dispatches_before_blocking_on_inflight(serving_setup):
    """A deadline-expired batch must come back from the worker's poll while
    the in-flight batch is still on the device — not after _finalize."""
    engine, *_ = serving_setup
    mb = MicroBatcher(engine, BatchPolicy(max_batch=16, max_wait_ms=1.0),
                      warmup_on_start=False)

    class _NeverReady:
        def is_ready(self):
            return False

    stuck = _InFlight(reqs=[], scores=_NeverReady(), labels=_NeverReady(),
                      t_dequeue=0.0, bucket=1, trigger=TRIGGER_SIZE)
    mb.queue.put(_req(t=time.perf_counter() - 1.0))  # deadline long past
    t0 = time.perf_counter()
    reqs, trigger = mb._poll_ready(stuck, 1e-3)
    assert trigger == TRIGGER_DEADLINE and len(reqs) == 1
    assert time.perf_counter() - t0 < 0.5  # did not wait on device results


# ---------------------------------------------------------------------------
# 5. fault injection: a dispatch error fails only its own batch
# ---------------------------------------------------------------------------

def test_dispatch_fault_fails_only_its_batch(serving_setup):
    engine, queries, ref_s, ref_l = serving_setup
    eng = XMRServingEngine(engine.tree, engine.config)
    calls = {"n": 0}
    real_run = eng._run

    def flaky_run(xi, xv, tier=0):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected device fault")
        return real_run(xi, xv, tier=tier)

    eng._run = flaky_run
    mb = MicroBatcher(eng, BatchPolicy(max_batch=16, max_wait_ms=5.0),
                      warmup_on_start=False)
    futs = mb.submit_csr(queries)  # 45 → batches 16/16/13; batch 2 faults
    mb.start()
    outcomes = []
    for i, f in enumerate(futs):
        try:
            s, l = f.result(timeout=60)
            np.testing.assert_array_equal(s, ref_s[i])
            np.testing.assert_array_equal(l, ref_l[i])
            outcomes.append("ok")
        except RuntimeError as exc:
            assert "injected device fault" in str(exc)
            outcomes.append("fault")
    # every future resolved exactly once: the faulted batch and nothing else
    assert outcomes == ["ok"] * 16 + ["fault"] * 16 + ["ok"] * 13
    # the worker survived — the queue keeps serving
    f2 = mb.submit(*queries.row(0))
    s, l = f2.result(timeout=60)
    np.testing.assert_array_equal(s, ref_s[0])
    mb.stop()


# ---------------------------------------------------------------------------
# 6. honest latency accounting
# ---------------------------------------------------------------------------

def test_amortized_batch_stats_stay_out_of_percentiles(serving_setup):
    """serve_batch's per-call average must not masquerade as per-query
    samples in the Table-4 percentile panel."""
    engine, queries, ref_s, ref_l = serving_setup
    eng = XMRServingEngine(engine.tree, engine.config)
    eng.serve_batch(queries)
    summ = eng.latency_summary()
    assert summ["count"] == 0 and "p99_ms" not in summ
    assert summ["amortized"]["calls"] == 1
    assert summ["amortized"]["queries"] == queries.shape[0]
    eng.serve_online(queries, limit=5)
    summ = eng.latency_summary()
    assert summ["count"] == 5 and "p99_ms" in summ  # 5 true per-query samples
    assert summ["amortized"]["calls"] == 1          # untouched by online mode


def test_latency_stats_record_routes_call_averages():
    from repro.serving.metrics import LatencyStats

    stats = LatencyStats()
    stats.record(0.010)                 # one true per-query sample
    stats.record(0.160, n_queries=16)   # legacy amortized record() call
    summ = stats.summary()
    assert summ["count"] == 1           # percentile series has ONE sample
    assert summ["p99_ms"] == pytest.approx(10.0)
    assert summ["amortized"]["calls"] == 1
    assert summ["amortized"]["avg_ms_per_query"] == pytest.approx(10.0)


@pytest.mark.slow
def test_poisson_stream_under_load(serving_setup):
    """Open-loop arrivals: every request resolves, metrics stay consistent."""
    engine, queries, ref_s, ref_l = serving_setup
    rng = np.random.default_rng(3)
    mb = MicroBatcher(engine, BatchPolicy(max_batch=8, max_wait_ms=1.0))
    mb.start()
    futs = []
    for i in range(queries.shape[0]):
        time.sleep(float(rng.exponential(2e-4)))
        futs.append(mb.submit(*queries.row(i)))
    res = [f.result(timeout=60) for f in futs]
    mb.stop()
    np.testing.assert_array_equal(np.stack([r[0] for r in res]), ref_s)
    s = mb.metrics.summary()
    assert s["count"] == queries.shape[0]
    assert sum(mb.metrics.batch_sizes) == queries.shape[0]


# ---------------------------------------------------------------------------
# 7. queue_depth="auto" capacity probe + lifecycle
# ---------------------------------------------------------------------------

def _auto_mb(engine, secs, monkeypatch, *, max_batch=16, deadline_ms=None):
    """Batcher with a deterministic drain-rate probe (not started)."""
    monkeypatch.setattr(
        engine, "measure_batch_seconds",
        lambda batch, iters=3, tier=0: secs,
    )
    return MicroBatcher(
        engine,
        BatchPolicy(max_batch=max_batch, max_wait_ms=2.0),
        admission=AdmissionPolicy(
            max_queue_depth="auto", deadline_ms=deadline_ms
        ),
    )


def test_auto_depth_floors_at_max_batch_when_drain_is_slow(
    serving_setup, monkeypatch
):
    """A near-zero drain rate must still admit one full bucket."""
    engine, *_ = serving_setup
    mb = _auto_mb(engine, 1e3, monkeypatch)  # 1000 s per bucket
    assert mb._auto_queue_depth() == 16


def test_auto_depth_zero_drain_time_is_finite(serving_setup, monkeypatch):
    """A probe measuring ~0 s (clock granularity) must not divide by zero
    or overflow — the bound resolves to a finite int."""
    engine, *_ = serving_setup
    mb = _auto_mb(engine, 0.0, monkeypatch)
    depth = mb._auto_queue_depth()
    assert isinstance(depth, int) and depth >= 16


def test_auto_depth_deadline_none_uses_coalescing_budget(
    serving_setup, monkeypatch
):
    """Without a per-request deadline the budget is ten deadline-trigger
    windows (10 x max_wait_ms); with one, the deadline itself."""
    engine, *_ = serving_setup
    # 16 ms per 16-query bucket -> 1000 QPS drain rate
    mb = _auto_mb(engine, 0.016, monkeypatch)
    assert mb._auto_queue_depth() == 20   # 1000 QPS * 10 * 2 ms
    mb = _auto_mb(engine, 0.016, monkeypatch, deadline_ms=50.0)
    assert mb._auto_queue_depth() == 50   # 1000 QPS * 50 ms


def test_auto_depth_sharded_bucket_floor(serving_setup, monkeypatch):
    """shards > 1 raises the bucket floor (a bucket always splits evenly
    over the mesh), which raises the measured drain rate with it."""
    engine, *_ = serving_setup
    mb = _auto_mb(engine, 0.008, monkeypatch, max_batch=2, deadline_ms=50.0)
    assert engine.bucket_for(2) == 2
    assert mb._auto_queue_depth() == 13   # 250 QPS * 50 ms, floored at 13
    monkeypatch.setattr(engine.config, "shards", 8)
    assert engine.bucket_for(2) == 8
    assert mb._auto_queue_depth() == 50   # 1000 QPS * 50 ms


def test_stop_during_auto_probe_waits_probe_out(serving_setup, monkeypatch):
    """stop() racing start()'s capacity probe must neither deadlock nor
    close the queue under the half-measured bucket: it waits for start to
    finish, then observes and joins the freshly started worker."""
    engine, *_ = serving_setup
    probe_entered = threading.Event()
    release_probe = threading.Event()

    def blocking_probe(batch, iters=3, tier=0):
        probe_entered.set()
        assert release_probe.wait(timeout=30), "probe never released"
        return 1e-3

    monkeypatch.setattr(engine, "measure_batch_seconds", blocking_probe)
    mb = MicroBatcher(
        engine,
        BatchPolicy(max_batch=16, max_wait_ms=2.0),
        admission=AdmissionPolicy(max_queue_depth="auto"),
        warmup_on_start=False,
    )
    starter = threading.Thread(target=mb.start)
    starter.start()
    assert probe_entered.wait(timeout=30)
    stopper = threading.Thread(target=mb.stop)
    stopper.start()
    # stop() is parked on the lifecycle lock: the queue must still be open
    # (closing it now would strand the probe's bucket half-measured).
    time.sleep(0.05)
    assert not mb.queue.closed
    release_probe.set()
    starter.join(timeout=30)
    stopper.join(timeout=30)
    assert not starter.is_alive() and not stopper.is_alive()
    # start completed its probe (bound resolved), stop joined the worker
    assert isinstance(mb.admission.max_queue_depth, int)
    assert mb.queue.closed and mb._thread is None


# ---------------------------------------------------------------------------
# 8. host spans of each dispatch
# ---------------------------------------------------------------------------

def _traced(tmp_path, fn):
    """``fn()`` under a profiler session; its result and the ``repro.*``
    host spans as ``(name, start_ns, end_ns, line, stats)``."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            found.extend(
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, i,
                 dict(ev.stats))
                for ev in line.events if ev.name.startswith("repro.")
            )
    return out, found


def test_serve_batch_bitwise_with_profiler_on_and_off(serving_setup, tmp_path):
    engine, queries, ref_s, ref_l = serving_setup
    off_s, off_l = engine.serve_batch(queries)
    (on_s, on_l), found = _traced(tmp_path, lambda: engine.serve_batch(queries))
    assert found  # the session recorded the spans
    np.testing.assert_array_equal(on_s, off_s)
    np.testing.assert_array_equal(on_l, off_l)
    np.testing.assert_array_equal(on_s, ref_s)


def _microbatched(engine, queries):
    mb = MicroBatcher(engine, BatchPolicy(max_batch=16, max_wait_ms=5.0),
                      warmup_on_start=False)
    futs = mb.submit_csr(queries)  # 45 → batches of 16, 16 and 13
    mb.start()
    out = [f.result(timeout=60) for f in futs]
    mb.stop()  # the worker's last spans end inside the session
    return out


@pytest.mark.parametrize("entry", ["serve_batch", "serve_online",
                                   "microbatcher"])
def test_spans_join_each_dispatch(serving_setup, tmp_path, entry):
    """Every dispatch has its marshal, enqueue, wait and fetch spans under
    one id on one thread, in that order; a micro-batch's marshal and
    enqueue nest in its batcher dispatch, which follows its forming and
    precedes its resolution."""
    engine, queries, *_ = serving_setup
    run = {"serve_batch": lambda: engine.serve_batch(queries),
           "serve_online": lambda: engine.serve_online(queries, limit=5),
           "microbatcher": lambda: _microbatched(engine, queries)}[entry]
    _, found = _traced(tmp_path, run)
    by_id = {}
    for name, start, end, line, stats in found:
        by_id.setdefault(stats["dispatch"], {}).setdefault(name, []).append(
            (start, end, line, stats))
    enqueued = [d for d, named in by_id.items() if spans.DISPATCH in named]
    assert len(enqueued) == {"serve_batch": 1, "serve_online": 5,
                             "microbatcher": 3}[entry]
    batched = entry == "microbatcher"
    for d in enqueued:
        named = by_id[d]
        want = {spans.MARSHAL, spans.DISPATCH, spans.WAIT, spans.FETCH}
        if batched:
            want |= {spans.FORM, spans.BATCH_DISPATCH, spans.RESOLVE}
        assert set(named) == want
        assert len({line for evs in named.values() for _, _, line, _ in evs}) == 1
        assert all(len(evs) == 1 for n, evs in named.items() if n != spans.FORM)
        one = {n: evs[-1] for n, evs in named.items()}
        order = [spans.MARSHAL, spans.DISPATCH, spans.WAIT, spans.FETCH]
        if batched:
            order.append(spans.RESOLVE)
        for a, b in zip(order, order[1:]):
            assert one[a][1] <= one[b][0], (a, b)
        assert one[spans.MARSHAL][3]["bucket"] >= one[spans.MARSHAL][3]["rows"]
        assert one[spans.DISPATCH][3]["tier"] == 0
        if batched:
            outer = one[spans.BATCH_DISPATCH]
            assert one[spans.FORM][1] <= outer[0]
            for inner in (spans.MARSHAL, spans.DISPATCH):
                assert outer[0] <= one[inner][0] <= one[inner][1] <= outer[1]
            assert one[spans.FORM][3]["requests"] == outer[3]["requests"]
            assert one[spans.RESOLVE][3]["requests"] == outer[3]["requests"]
