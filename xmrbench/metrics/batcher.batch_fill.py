"""Real queries over the jit bucket slots dispatched for them, over the
window's micro-batches."""


def read(run):
    if run.batcher is None or not run.batcher["bucket_sizes"].sum():
        return None
    b = run.batcher
    return 100.0 * float(b["batch_sizes"].sum()) / float(b["bucket_sizes"].sum())
