"""Median latency over every request due in the window, each from its
scheduled send time to the resolution of its future (online cells)."""

import numpy as np


def read(run):
    if run.latencies_ms is None or not np.isfinite(
            np.percentile(run.latencies_ms, 50)):
        return None
    return float(np.percentile(run.latencies_ms, 50))
