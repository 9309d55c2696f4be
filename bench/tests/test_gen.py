"""The one traffic generator: the same seed gives the same batches, every
seed the same sizes, and each distribution keeps to its parameters."""
from __future__ import annotations

import numpy as np

import gen

N = 4096
SEED = 2 ** 33 + 5


def _config(*columns):
    return {"data": {"columns": list(columns)}}


def _make(config, seed=SEED, pool=2):
    return [[np.asarray(c) for c in b] for b in
            gen.batches(config, {"push_tuples": N, "pool": pool}, seed)]


def test_same_seed_same_batches_other_seed_other_values():
    conf = _config({"name": "groups", "dist": "uniform", "low": 0,
                    "high": 64})
    a, b, c = _make(conf), _make(conf), _make(conf, SEED + 2 ** 32)
    np.testing.assert_array_equal(a[0][0], b[0][0])
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], a[1][0])
    assert a[0][0].shape == c[0][0].shape == (N,)
    assert a[0][0].dtype == np.int32


def test_distributions_keep_their_parameters():
    conf = _config(
        {"name": "a", "dist": "uniform", "low": 5, "high": 9},
        {"name": "b", "dist": "zipf", "high": 100, "s": 1.2},
        {"name": "c", "dist": "constant", "value": 3},
        {"name": "d", "dist": "event_time", "step": 10, "late_share": 0.1,
         "late_max": 300})
    a, b, c, d = _make(conf, pool=1)[0]
    assert a.min() >= 5 and a.max() < 9
    assert b.min() >= 0 and b.max() < 100
    counts = np.bincount(b, minlength=100)
    assert counts[0] > counts[1] > counts[10] and counts[0] > 0.15 * N
    assert (c == 3).all()
    on_time = np.arange(N) * 10
    late = d < on_time
    assert 0.05 * N < late.sum() < 0.15 * N
    assert (on_time - d).max() <= 300 and d.min() >= 0
    assert (d[~late] == on_time[~late]).all()


def test_column_names():
    conf = _config({"name": "groups", "dist": "constant", "value": 0},
                   {"name": "keys", "dtype": "int32", "dist": "uniform",
                    "low": 0, "high": 2})
    assert gen.column_names(conf) == ("groups", "keys")
