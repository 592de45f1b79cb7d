"""The one traffic generator: query widths, target skew and send times,
each drawn from the seed."""

import numpy as np
import pytest

from xmrbench_testkit import REPO  # noqa: F401  (sys.path)

from xmrbench import generator, weights

SPEC = {"model": "label_path", "targets": {"kind": "uniform"},
        "path_share": 0.5, "zipf_a": 1.3, "value_offset": 0.05}


@pytest.fixture(scope="module")
def levels():
    return weights.tree_weights(np.random.SeedSequence(5), 3000, 5000, 32,
                                [64, 64, 64], 0.8)


@pytest.mark.parametrize("query_nnz", [40, 200])
def test_every_query_holds_query_nnz_distinct_ids(levels, query_nnz):
    qi, qx, qv = generator.label_path_queries(
        np.random.default_rng(2**33 + 1), levels, 32, 500, query_nnz, SPEC)
    assert (np.diff(qi) == query_nnz).all()
    ids = qx.reshape(500, query_nnz)
    assert (np.diff(ids, axis=1) > 0).all()
    assert ids.min() >= 0 and ids.max() < 3000
    assert (qv >= 0.05).all()
    again = generator.label_path_queries(
        np.random.default_rng(2**33 + 1), levels, 32, 500, query_nnz, SPEC)
    assert all((a == b).all() for a, b in zip((qi, qx, qv), again))


def test_first_distinct_keeps_the_first_of_each_id():
    ids = np.array([[3, 1, 3, 2, 1], [0, 0, 0, 5, 4]])
    assert generator.first_distinct(ids).tolist() == [
        [True, True, False, True, False], [True, False, False, True, True]]


def test_zipf_targets_share_labels_and_uniform_ones_spread():
    rng = np.random.default_rng(9)
    uniform = generator.target_labels(rng, 670091, 4096, {"kind": "uniform"})
    skewed = generator.target_labels(rng, 670091, 4096,
                                     {"kind": "zipf", "a": 1.2})
    assert len(np.unique(uniform)) > 4000
    assert len(np.unique(skewed)) < 2000
    assert 0 <= skewed.min() and skewed.max() < 670091
    with pytest.raises(ValueError):
        generator.target_labels(rng, 10, 4, {"kind": "no-such"})


def test_poisson_sends_a_fixed_count():
    for seed in (1, 2**31 + 7):
        t = generator.arrival_offsets(np.random.default_rng(seed),
                                      {"kind": "poisson"}, 440.0, 51.0)
        assert len(t) == 22440 and (np.diff(t) >= 0).all()
        assert 0 <= t[0] and t[-1] < 51.0


def test_bursts_send_a_fixed_count_in_each_phase():
    arrivals = {"kind": "bursts", "on_s": 2.0, "off_s": 3.0, "on_x": 2.0,
                "off_x": 0.5}
    t = generator.arrival_offsets(np.random.default_rng(3), arrivals,
                                  100.0, 12.0)
    # Phases [0,2) [2,5) [5,7) [7,10) [10,12) at 200, 50, 200, 50, 200/s.
    counts = np.histogram(t, bins=[0, 2, 5, 7, 10, 12])[0]
    assert counts.tolist() == [400, 150, 400, 150, 400]
    assert (np.diff(t) >= 0).all()


def test_closed_loop_arrivals_have_no_send_times():
    with pytest.raises(ValueError):
        generator.arrival_offsets(np.random.default_rng(1),
                                  {"kind": "blocks", "block": 8}, 1.0, 1.0)
    assert set(generator.ENTRIES.values()) == {"serve_batch", "serve_online",
                                               "microbatcher"}
