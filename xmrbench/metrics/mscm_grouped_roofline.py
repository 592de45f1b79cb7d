"""The grouped MSCM kernel's share of its roofline: the least time the
window's kernel work needs on this chip (``work/mscm_grouped.py``: every
level of every dispatch, at the larger of its bytes over peak bandwidth
and its operations over peak FLOP/s; bytes bound it here) over the summed
device time of the kernel's events in the window.

The program gives the kernel no name of its own: a Pallas kernel shows in
the trace as an HLO ``custom-call`` with ``custom_call_target=
"tpu_custom_call"``, and on the grouped path (``method
"mscm_pallas_grouped"``, one kernel call per level) it is the only one."""

from xmrbench.trace import clip, total_ns

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or run.work() is None:
        return None
    lo, hi = run.trace.window
    ns, n = total_ns(clip(run.device_events(), lo, hi),
                     lambda name: KERNEL in name)
    if not n:
        return None
    return 100.0 * run.work()["kernel_least_s"] / (ns * 1e-9)
