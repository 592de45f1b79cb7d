"""Device time of the beam-search program (``core/tree.py::_tree_infer``,
one jit) per execution in the window."""

from xmrbench.trace import clip, total_ns

PROGRAM = "_tree_infer"


def read(run):
    if run.trace is None or run.latencies_ms is None:
        return None
    lo, hi = run.trace.window
    ns, n = total_ns(clip(run.module_events(), lo, hi),
                     lambda name: PROGRAM in name)
    return ns * 1e-6 / n if n else None
