"""The whole step's share of the chip's peak: the least time the window's
work needs on this chip (``work/mscm_grouped.py``: every level of every
dispatch; bandwidth bounds it at these sizes) over the window's length."""


def read(run):
    if run.trace is None or run.work() is None:
        return None
    return 100.0 * run.work()["step_least_s"] / run.trace.window_s
