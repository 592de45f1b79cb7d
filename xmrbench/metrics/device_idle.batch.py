"""Share of the traced window in which no operation ran on the device."""

from xmrbench.trace import idle_share


def read(run):
    if run.trace is None or run.latencies_ms is not None:
        return None
    events = run.device_events()
    if not events:
        return None
    lo, hi = run.trace.window
    return 100.0 * idle_share(events, lo, hi)
