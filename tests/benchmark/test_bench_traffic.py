"""The one traffic generator: a deployment's past and its windows."""

import json
import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mix():
    with open(os.path.join(REPO, "benchmark", "traffic", "tiger_steady.json")) as f:
        mix = json.load(f)
    mix.update(preroll_requests=3000, n_users=500, rate_per_s=100.0)
    return mix


def _trace(seed=2**31 + 3, seconds=2.0, window=0, **over):
    from benchmark.harness.traffic import deployment_trace

    return deployment_trace({**_mix(), **over}, seconds, 20, 1000, seed,
                            cache_entries=200, window=window)


def _shape(arrivals):
    return [(a.due_s, len(a.history), a.repeat) for a in arrivals]


def test_fill_is_the_newest_distinct_requests_of_the_past():
    fill, arrivals = _trace()
    keys = [(a.user_id, tuple(a.history)) for a in fill]
    assert len(keys) == len(set(keys)) == 200
    assert len(arrivals) == 200 and all(0 < a.due_s < 2.0 for a in arrivals)
    # A long past: most of what the cache holds are full histories.
    assert np.mean([len(a.history) == 20 for a in fill]) > 0.5
    # A repeat in the window resends that user's last request unchanged.
    last = {a.user_id: a.history for a in fill}
    for a in arrivals:
        if a.repeat and a.user_id in last:
            assert np.array_equal(a.history, last[a.user_id])
        last[a.user_id] = a.history


def test_the_seed_changes_the_content_and_never_the_shape():
    fill_a, arr_a = _trace(seed=5)
    fill_b, arr_b = _trace(seed=2**31 + 99)
    assert _shape(arr_a) == _shape(arr_b)
    assert [len(a.history) for a in fill_a] == [len(a.history) for a in fill_b]
    assert [a.user_id for a in arr_a] != [a.user_id for a in arr_b]
    again, arr_again = _trace(seed=5)
    assert all(np.array_equal(x.history, y.history) and x.user_id == y.user_id
               for x, y in zip(fill_a + arr_a, again + arr_again))


def test_the_past_depends_on_nothing_of_the_window():
    fill, arr0 = _trace()
    for over in ({"window": 1}, {"seconds": 5.0}, {"rate_per_s": 37.0}):
        other, arr = _trace(**over)
        assert all(np.array_equal(x.history, y.history) and x.user_id == y.user_id
                   for x, y in zip(fill, other))
        assert _shape(arr) != _shape(arr0)


def test_no_cache_means_no_past():
    from benchmark.harness.traffic import deployment_trace

    fill, arrivals = deployment_trace(_mix(), 2.0, 20, 1000, 7)
    assert fill == [] and len(arrivals) == 200
    # Nobody has been seen before: every first request is a first length.
    assert np.mean([len(a.history) for a in arrivals]) < 14
