"""99th percentile of the micro-batcher's queue wait (enqueue to batch
formed, ``ServerMetrics.queue_wait_ms``) over the window's requests."""

import numpy as np


def read(run):
    if run.batcher is None or not len(run.batcher["queue_wait_ms"]):
        return None
    return float(np.percentile(run.batcher["queue_wait_ms"], 99))
