"""Shared test fixtures/helpers.

NOTE: no XLA device-count flags here — tests see the real single CPU device.
Only launch/dryrun.py (run as a script) forces 512 placeholder devices.
"""

import numpy as np
import pytest


def _has_tpu() -> bool:
    import jax

    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``tpu``-marked tests when no TPU device is present.

    ``slow`` is a plain registered marker — deselect with ``-m "not slow"``
    (what CI does); it carries no auto-skip so a full local run still
    exercises everything.
    """
    tpu_items = [item for item in items if "tpu" in item.keywords]
    if not tpu_items or _has_tpu():
        return  # don't initialize the JAX backend unless the marker is used
    skip_tpu = pytest.mark.skip(reason="no TPU device present")
    for item in tpu_items:
        item.add_marker(skip_tpu)


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


#: Cross-method score tolerance. Within one method results are bitwise
#: identical; across methods each sums the chunk dot products in its own
#: order (XLA einsum, Pallas tile matmul, per-column dots), so scores may
#: differ in their last bits. Four f32 ulp, relative to the largest score
#: (a logit near 0 is a difference of large terms).
CROSS_METHOD_RTOL = 4 * float(np.finfo(np.float32).eps)


def assert_cross_method_close(got, want):
    """Scores of two methods agree within :data:`CROSS_METHOD_RTOL`."""
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.abs(want).max(initial=0.0))
    np.testing.assert_allclose(
        got, want, rtol=CROSS_METHOD_RTOL, atol=CROSS_METHOD_RTOL * scale
    )


def make_tree_weights(rng, d, level_sizes, branching, nnz_per_col=10):
    """Random per-level CSC weight matrices with sibling-correlated support."""
    from repro.sparse import random_sparse_csc

    return [
        random_sparse_csc(d, L, nnz_per_col, rng, sibling_groups=branching)
        for L in level_sizes
    ]


def brute_force_scores(X_dense, weights):
    """Dense full-tree scores (paper eq. 5) — the exactness oracle."""
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    prev = np.ones((X_dense.shape[0], 1), np.float32)
    for w in weights:
        act = sig(X_dense @ w.to_dense())
        b = act.shape[1] // prev.shape[1]
        prev = np.repeat(prev, b, axis=1) * act
    return prev
