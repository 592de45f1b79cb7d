"""Queries answered in the window over the window's length (batch cells)."""


def read(run):
    if run.latencies_ms is not None or run.window_s <= 0:
        return None
    return run.queries / run.window_s
