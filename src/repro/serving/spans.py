"""Named host spans of the serving path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``. It records only while a
profiler session is active (``jax.profiler.trace(dir)`` around a running
server), into the same trace as the device's operations; outside a
session it costs one Python call. Every span carries ``dispatch=<id>``,
the engine-wide sequence number of the dispatch it belongs to
(:meth:`~repro.serving.engine.XMRServingEngine.next_dispatch_id`), so one
dispatch's spans can be joined across the trace.

===========================  ==============================================
span                         covers
===========================  ==============================================
``repro.engine.marshal``     CSR→ELL rows, bucket padding, host→device copy
``repro.engine.dispatch``    the asynchronous enqueue of the beam search
``repro.engine.wait``        blocked until the dispatch's results are ready
``repro.engine.fetch``       device→host copy of the results, label map
``repro.batcher.form``       the worker waiting for a trigger or the
                             in-flight batch, forming the next batch
``repro.batcher.dispatch``   tier choice, request rows to CSR, marshal and
                             enqueue of one micro-batch
``repro.batcher.resolve``    futures resolved (done-callbacks included) and
                             the batch's metrics recorded
===========================  ==============================================
"""

from __future__ import annotations

import jax

MARSHAL = "repro.engine.marshal"
DISPATCH = "repro.engine.dispatch"
WAIT = "repro.engine.wait"
FETCH = "repro.engine.fetch"
FORM = "repro.batcher.form"
BATCH_DISPATCH = "repro.batcher.dispatch"
RESOLVE = "repro.batcher.resolve"


def span(name: str, dispatch: int, **stats) -> jax.profiler.TraceAnnotation:
    """A host span of dispatch ``dispatch``; use it as a context manager.

    ``stats`` are recorded beside the span; more can be added inside it
    with ``set_metadata(**stats)``.
    """
    return jax.profiler.TraceAnnotation(name, dispatch=dispatch, **stats)
