"""The reduction of the program's own spans: on a synthetic trace with known
answers, and on traced CPU windows of the tiny cells."""

import pytest

from xmrbench_testkit import tiny_root

from xmrbench import program_spans as ps
from xmrbench import trace


def _span(name, start, end, dispatch=None, thread="python/1"):
    stats = {} if dispatch is None else {"dispatch": dispatch}
    return ps.Span(name, start, end, thread, stats)


# The batcher's worker (python/1) forms, dispatches and resolves two
# micro-batches, double-buffered; the generator (python/0) waits for its
# next arrival. Times in ns.
WORKER = [
    _span(ps.FORM, 0, 100, 0),
    _span(ps.BATCH_DISPATCH, 100, 160, 0),
    _span(ps.MARSHAL, 110, 140, 0),
    _span(ps.DISPATCH, 140, 155, 0),
    _span(ps.FORM, 160, 300, 1),
    _span(ps.WAIT, 300, 320, 0),
    _span(ps.FETCH, 320, 330, 0),
    _span(ps.RESOLVE, 330, 350, 0),
    _span(ps.FORM, 350, 500, 1),
    _span(ps.BATCH_DISPATCH, 500, 540, 1),
    _span(ps.MARSHAL, 505, 520, 1),
    _span(ps.DISPATCH, 520, 530, 1),
    _span(ps.FORM, 540, 700, 2),
    _span(ps.WAIT, 700, 760, 1),
    _span(ps.FETCH, 760, 770, 1),
    _span(ps.RESOLVE, 770, 790, 1),
]
GENERATOR = [
    _span("xmrbench.window", 0, 1000, thread="python/0"),
    _span("xmrbench.await_arrival", 400, 480, thread="python/0"),
    _span("xmrbench.await_arrival", 800, 900, thread="python/0"),
]
SPANS = WORKER + GENERATOR
OPS = [("fusion.1", 170, 290), ("fusion.2", 540, 690)]
MODULES = [("jit__tree_infer(3)", 170, 290), ("jit__tree_infer(3)", 540, 690)]


def test_every_dispatch_has_its_spans():
    assert ps.missing(SPANS, ps.ENGINE + ps.BATCHER, 0, 1000) == {
        n: 0 for n in ps.ENGINE + ps.BATCHER}
    without = [s for s in SPANS
               if not (s.name == ps.FETCH and s.stats["dispatch"] == 1)]
    assert ps.missing(without, ps.ENGINE, 0, 1000)[ps.FETCH] == 1


def test_host_ms_per_dispatch_leaves_out_waits_and_forming():
    # Host work: 100-160, 320-350, 500-540, 760-790 = 160 ns, two dispatches.
    assert ps.host_ms_per_dispatch(SPANS, 0, 1000) == pytest.approx(80e-6)
    # Only dispatches started in the window count; work is clipped to it.
    assert ps.host_ms_per_dispatch(SPANS, 0, 400) == pytest.approx(90e-6)
    assert ps.host_ms_per_dispatch(GENERATOR, 0, 1000) is None


def test_inflight_and_its_parts():
    assert ps.inflight_ms(SPANS, 0, 1000) == pytest.approx([165e-6, 230e-6])
    split = ps.device_split_ms(SPANS, MODULES, 0, 1000)
    assert split["queued"] == pytest.approx([15e-6, 10e-6])
    assert split["device"] == pytest.approx([120e-6, 150e-6])
    assert split["notice"] == pytest.approx([30e-6, 70e-6])
    # A dispatch whose program was not recorded is left out.
    split = ps.device_split_ms(SPANS, MODULES[1:], 0, 1000)
    assert split == {
        "queued": [pytest.approx(10e-6)], "device": [pytest.approx(150e-6)],
        "notice": [pytest.approx(70e-6)]}


def test_gaps_named_by_the_workers_span_first():
    gaps = ps.label_gaps(OPS, SPANS, 0, 1000)
    # Gaps: [690,1000) 310, [290,540) 250, [0,170) 170.
    assert [g["ms"] for g in gaps] == pytest.approx([310e-6, 250e-6, 170e-6])
    # At 845 the worker holds no span: the generator's names the gap. At
    # 415 the worker forms a batch while the generator, which started its
    # wait later, waits for an arrival: the program's span wins.
    assert [g["label"] for g in gaps] == [
        "xmrbench.await_arrival", ps.FORM, ps.FORM]
    assert gaps[1]["threads"] == {"python/0": "xmrbench.await_arrival",
                                  "python/1": ps.FORM}


def test_gaps_without_program_spans_named_as_the_harness_names_them():
    ops = [("a", 100, 300), ("b", 600, 700)]
    host = [("xmrbench.window", 0, 1000), ("xmrbench.serve_batch", 50, 450),
            ("xmrbench.await_arrival", 450, 1000)]
    spans = [_span(n, s, e, thread="python/0") for n, s, e in host]
    got = ps.label_gaps(ops, spans, 0, 1000)
    want = trace.idle_gaps(ops, host, 0, 1000)
    assert [g["label"] for g in got] == [label for label, _ in want]
    assert [g["ms"] * 1e-3 for g in got] == pytest.approx([s for _, s in want])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("xmrbench_spans"))


@pytest.mark.parametrize("workload,batched", [("tiny.batch", False),
                                              ("tiny.online", True)])
def test_traced_window_reads_every_dispatch(root, workload, batched):
    out = ps.measure(workload, 2**33 + 9, 0.5, root=root, allow_cpu=True)
    names = ps.ENGINE + (ps.BATCHER if batched else ())
    assert out["dispatches"] > 0
    # A span records only if it starts inside the session: the worker's
    # first forming may have begun before it.
    lost = {n: 1 if n == ps.FORM else 0 for n in names}
    assert all(out["missing"][n] <= lost[n] for n in names)
    assert out["host_ms_per_dispatch"] > 0
    assert 0 < out["inflight_ms"]["p50"] <= out["inflight_ms"]["max"]
    assert all(out["p50_ms"][n] is not None for n in names)
    assert set(out["window"]) == ({"latency_p50_ms", "queue_wait_p50_ms"}
                                  if batched else {"queries_per_s"})
    assert out["device"]["platform"] == "cpu"
    assert out["split_dispatches"] == 0   # no device plane on the CPU
    assert out["gaps"][0]["label"].startswith(("repro.", "xmrbench."))
