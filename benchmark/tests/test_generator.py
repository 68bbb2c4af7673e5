"""The load generator: a seed repeats its requests, and every seed gets
the same work in another order, over one fixed trace of arrivals."""

import numpy as np

from benchmark import generator

MIX = {"rate_per_s": 40.0, "reps": [1024, 2048]}


def test_arrivals_repeat_for_a_seed():
    a = generator.open_loop(MIX, 3141592653, 5.0, 20)
    b = generator.open_loop(MIX, 3141592653, 5.0, 20)
    assert a == b
    assert len(a) == 200


def test_every_seed_gets_the_same_work():
    a = generator.open_loop(MIX, 1, 5.0, 20)
    b = generator.open_loop(MIX, 2**32 + 5, 5.0, 20)
    assert a != b
    for key in ("row", "reps"):
        assert sorted(getattr(x, key) for x in a) == sorted(getattr(x, key) for x in b)
    # the same instants; sizes and scenarios in balanced blocks
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(0.0 <= x.due_s < 5.0 for x in a)
    assert abs(np.mean(np.diff([x.due_s for x in a])) - 1 / 40.0) < 1e-3
    for i in range(0, len(a), 2):
        assert {a[i].reps, a[i + 1].reps} == {1024, 2048}
    for i in range(0, 200, 20):
        assert sorted(x.row for x in b[i:i + 20]) == list(range(20))


def test_experiment_seeds_differ_and_repeat():
    s = [generator.experiment_seed(2**31 + 11, i) for i in range(4)]
    assert len(set(s)) == 4
    assert s == [generator.experiment_seed(2**31 + 11, i) for i in range(4)]
    assert all(0 <= x < 2**62 for x in s)
