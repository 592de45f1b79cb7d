"""The trace reduction on a synthetic event list with known answers."""

import pytest

from xmrbench_testkit import REPO  # noqa: F401  (sys.path)

from xmrbench import trace

# Device ops (ns): two overlapping, one nested, one outside the window.
OPS = [
    ("fusion.1", 100, 300),
    ("_grouped_body", 250, 400),     # overlaps fusion.1
    ("sort.2", 260, 280),            # nested in both
    ("_grouped_body", 600, 700),
    ("copy.3", 1200, 1300),          # after the window
]
MODULES = [("jit__tree_infer(7)", 100, 400), ("jit__tree_infer(7)", 600, 700)]
HOST = [
    ("xmrbench.window", 0, 1000),
    ("xmrbench.serve_batch", 50, 450),
    ("xmrbench.await_arrival", 450, 1000),
]


def test_union_and_busy():
    assert trace.union(OPS[:4]) == [(100, 400), (600, 700)]
    assert trace.busy_ns(OPS, 0, 1000) == 400
    assert trace.busy_ns(OPS, 350, 650) == 100     # clipped at both ends
    assert trace.idle_share(OPS, 0, 1000) == pytest.approx(0.6)


def test_program_and_kernel_time():
    assert trace.total_ns(MODULES, lambda n: "_tree_infer" in n) == (400, 2)
    in_window = trace.clip(OPS, 0, 1000)
    assert trace.total_ns(in_window, lambda n: "_grouped_body" in n) == (250, 2)
    top = trace.top_ops(in_window, k=2)
    assert top[0] == ("_grouped_body", pytest.approx(250e-9))
    assert top[1] == ("fusion.1", pytest.approx(200e-9))


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(OPS, HOST, 0, 1000)
    # Gaps: [0,100) 100, [400,600) 200, [700,1000) 300; longest first.
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 200e-9, 100e-9])
    assert [g[0] for g in gaps] == ["xmrbench.await_arrival",
                                    "xmrbench.await_arrival",
                                    "xmrbench.serve_batch"]
    assert trace.idle_gaps(OPS, [], 0, 1000, k=1) == [("idle",
                                                       pytest.approx(300e-9))]
