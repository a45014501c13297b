"""The traffic generator: determinism, popularity shares, rotation."""
import numpy as np
import pytest

from harness import traffic as tr

MIX = {"functions": {"weight_seeds": [0, 1, 2, 3]},
       "popularity": {"law": "zipf", "s": 1.0},
       "rotation_s": 0, "rate": {"inv_s": 50.0}, "arrival_seed": 9}


def test_same_seed_same_schedule_and_large_seeds():
    seed = 2**31 + 12345
    assert tr.schedule(MIX, seed, 10.0) == tr.schedule(MIX, seed, 10.0)
    assert tr.schedule(MIX, seed, 10.0) != tr.schedule(MIX, seed + 1, 10.0)


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 5])
def test_every_seed_gets_the_same_count_inside_the_window(seed):
    arr = tr.schedule(MIX, seed, 12.0)
    assert len(arr) == 600
    ts = [a.t for a in arr]
    assert ts == sorted(ts) and 0 <= ts[0] and ts[-1] < 12.0
    assert all(0 <= a.request_seed < 2**31 - 1 for a in arr)


def test_zipf_shares_are_exact_for_every_seed():
    np.testing.assert_allclose(tr.shares(MIX), np.array([12, 6, 4, 3]) / 25)
    for seed in (3, 4):
        arr = tr.schedule(MIX, seed, 10.0)
        counts = np.bincount([a.fn for a in arr], minlength=4)
        assert sorted(counts.tolist(), reverse=True) == [240, 120, 80, 60]


def test_every_seed_gets_the_same_times_and_other_functions():
    a, b = tr.schedule(MIX, 1, 10.0), tr.schedule(MIX, 2, 10.0)
    assert [x.t for x in a] == [x.t for x in b]
    assert [x.fn for x in a] != [x.fn for x in b]
    # the same queues: seed 2's functions are seed 1's, relabelled
    relabel = {x.fn: y.fn for x, y in zip(a, b)}
    assert len(relabel) == 4 and sorted(relabel.values()) == [0, 1, 2, 3]
    assert all(relabel[x.fn] == y.fn for x, y in zip(a, b))
    assert [x.request_seed for x in a] != [x.request_seed for x in b]
    other = tr.schedule(dict(MIX, arrival_seed=10), 1, 10.0)
    assert [x.t for x in other] != [x.t for x in a]
    # exponential gaps: e**-1 of them above the mean
    g = np.diff([x.t for x in a])
    assert abs(np.mean(g > g.mean()) - np.exp(-1)) < 0.03


def test_ranking_rotates_by_one_function_each_period():
    mix = dict(MIX, rotation_s=5.0)
    arr = tr.schedule(mix, 11, 400.0)
    tops = []
    for period in range(8):
        fns = [a.fn for a in arr if int(a.t // 5.0) % 8 == period]
        tops.append(int(np.bincount(fns, minlength=4).argmax()))
    assert len(set(tops[:4])) == 4 and tops[4:] == tops[:4]
    second = [int(np.argsort(np.bincount(
        [a.fn for a in arr if int(a.t // 5.0) % 8 == period],
        minlength=4))[-2]) for period in range(4)]
    assert second == tops[1:5]


def test_function_ids_name_config_and_weight_seed():
    assert tr.function_ids("qwen3-1.7b", MIX) == [
        "qwen3-1.7b/w0", "qwen3-1.7b/w1", "qwen3-1.7b/w2", "qwen3-1.7b/w3"]


def test_feeder_never_releases_early():
    import time

    class Inv:
        def __init__(self):
            self.arrival = time.monotonic()

    arr = tr.schedule(dict(MIX, rate={"inv_s": 100.0}), 5, 0.5)
    origin = time.monotonic() + 0.05
    f = tr.Feeder(arr, origin, lambda a: Inv())
    f.start()
    f.join(timeout=10)
    assert not f.is_alive() and f.error is None
    assert len(f.released) == len(arr)
    assert all(r.release >= r.due for r in f.released)
