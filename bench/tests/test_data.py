"""The benchmark's copies of the data generators still give the arrays
the program's own generators give for a seed."""
import numpy as np
import pytest

from bench import data
from repro.data import synthetic


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_tweet_latitudes_match(seed):
    np.testing.assert_array_equal(data.tweet_latitudes(5000, seed),
                                  synthetic.tweet_latitudes(5000, seed))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_hki_series_match(seed):
    for a, b in zip(data.hki_series(5000, seed),
                    synthetic.hki_series(5000, seed)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("selectivity", [None, 0.01])
def test_make_queries_1d_match(selectivity):
    keys = data.tweet_latitudes(5000, 3)
    for a, b in zip(data.make_queries_1d(keys, 300, 11, selectivity),
                    synthetic.make_queries_1d(keys, 300, 11, selectivity)):
        np.testing.assert_array_equal(a, b)


def test_seed_sequence_streams_differ():
    from bench.harness import seeds
    a = data.tweet_latitudes(100, seeds(5, 0))
    b = data.tweet_latitudes(100, seeds(5, 1))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, data.tweet_latitudes(100, seeds(5, 0)))
