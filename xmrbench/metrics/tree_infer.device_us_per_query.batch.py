"""Device time of the beam-search program (``core/tree.py::_tree_infer``,
one jit) in the window, per query answered."""

from xmrbench.trace import clip, total_ns

PROGRAM = "_tree_infer"


def read(run):
    if run.trace is None or run.latencies_ms is not None or not run.queries:
        return None
    lo, hi = run.trace.window
    ns, n = total_ns(clip(run.module_events(), lo, hi),
                     lambda name: PROGRAM in name)
    return ns * 1e-3 / run.queries if n else None
