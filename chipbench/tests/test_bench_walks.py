"""The traffic's walks over the drift chain: the order the window solves
the links in, drawn from the seed."""
import itertools

import numpy as np
import pytest

from chipbench import run


def _take(walk, k, seed, count):
    return list(itertools.islice(walk(k, np.random.default_rng(seed)), count))


def test_pingpong_goes_to_the_end_and_back():
    assert _take(run.walk_pingpong, 4, 0, 8) == [1, 2, 3, 2, 1, 0, 1, 2]


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_shuffle_passes_cover_every_link_once(seed):
    k = 20
    links = _take(run.walk_shuffle, k, seed, 5 * (k - 1))
    for p in range(5):
        assert sorted(links[p * (k - 1):(p + 1) * (k - 1)]) == \
            list(range(1, k))
    assert all(a != b for a, b in zip(links, links[1:]))
    assert links == _take(run.walk_shuffle, k, seed, 5 * (k - 1))
    assert links != _take(run.walk_shuffle, k, seed + 1, 5 * (k - 1))


def test_unknown_walk_is_refused():
    with pytest.raises(run.BenchError, match="walk"):
        run.Workload({"n": 16, "avg_degree": 4, "kind": "uniform",
                      "solve_options": {}},
                     {"walk": "spiral", "start": "cold", "loop": "closed"}, 1)
