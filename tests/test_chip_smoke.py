"""chip_smoke.py: refuses to run without a TPU, and its numpy reference
agrees with the served tree (and catches what it should)."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    from benchmarks.common import build_benchmark_weights
    from repro.core import XMRTree
    from repro.data.xmr_data import XMRShape

    shape = XMRShape("tiny", 4000, 30_000, 16, 75, 64)
    rng = np.random.default_rng(7)
    weights = build_benchmark_weights(shape, smoke.BRANCHING, rng)
    tree = XMRTree.from_weight_matrices(weights, smoke.BRANCHING)
    queries = smoke.path_queries(weights, shape.L, 12, shape.query_nnz, rng)
    xi, xv = map(jnp.asarray, queries.to_ell(256))
    return weights, tree, queries, xi, xv


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU present" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("method", ["vanilla", "mscm_dense"])
def test_reference_agrees_with_tree(smoke, served, method):
    weights, tree, queries, xi, xv = served
    s, l = tree.infer(xi, xv, beam=smoke.BEAM, topk=smoke.TOPK, method=method)
    checked, worst = smoke.check_against_reference(
        weights, queries, np.asarray(s), np.asarray(l))
    assert checked >= 10
    assert worst < smoke.RTOL


def test_reference_catches_wrong_results(smoke, served):
    weights, tree, queries, xi, xv = served
    s, l = tree.infer(xi, xv, beam=smoke.BEAM, topk=smoke.TOPK)
    s, l = np.asarray(s), np.asarray(l)
    swapped = l.copy()
    swapped[0, [1, 2]] = swapped[0, [2, 1]]
    with pytest.raises(AssertionError, match="labels"):
        smoke.check_against_reference(weights, queries, s, swapped)
    with pytest.raises(AssertionError):
        smoke.check_against_reference(weights, queries, s * (1 + 10 * smoke.RTOL), l)
