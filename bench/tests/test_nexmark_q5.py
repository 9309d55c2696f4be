"""The ``nexmark-q5.stream`` cell on the CPU: the harness's whole run at
a tiny cut is correct and its reference's control is caught; the stream
loop samples only pushes that emit a window, numbered from the stream's
start; and the entry's int32 bid arithmetic matches the reference's
int64 one past 2^31 bids."""
from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np
import pytest

import check
import harness
import manifest

NAME = "nexmark-q5.stream"
SEED = 2 ** 33 + 15


def tiny_cell() -> manifest.Cell:
    """Windows of 100 ms every 50 ms, 30 ms of lateness, pushes of 1,024
    bids (1.024 ms): a window closes every ~49 pushes.  The bid rate, the
    auction rule and the late share are the cell's own."""
    cell = manifest.cell(manifest.load(), NAME)
    config = copy.deepcopy(cell.config)
    config["query"]["window"] = {"range": 100, "slide": 50,
                                 "max_lateness": 30, "capacity": 16384}
    config["stream"]["max_delay_ms"] = 30
    delay = next(c for c in config["data"]["columns"]
                 if c["name"] == "delay")
    delay.update(low=-270, high=31)
    traffic = dict(cell.traffic, push_tuples=1024, warmup_pushes=160)
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture(scope="module")
def run_line():
    cell = tiny_cell()
    return cell, harness.run(cell, SEED, 0.5, False,
                             t_process=time.perf_counter(),
                             require_chip=False)


def test_tiny_run_is_correct(run_line):
    _, line = run_line
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["mismatched_elements"]["value"] == 0
    assert line["checks"]["pushes_compared"]["value"] >= 1
    assert line["metrics"]["tuples_per_s"]["value"] > 0


def test_control_is_caught():
    cell = tiny_cell()
    ref = cell.reference()
    import gen
    pool = [dict(zip(gen.column_names(cell.config),
                     (np.asarray(x) for x in b)))
            for b in gen.batches(cell.config, cell.traffic, SEED)]
    q = cell.config["query"]
    n = cell.traffic["push_tuples"]
    stamps = np.maximum.accumulate(ref._push_max(pool, 300, n))
    pushes = [p for p, ends in ref._schedule(q, stamps).items() if ends][:2]
    assert len(pushes) == 2
    want = ref.evaluate(pool, pushes, q)
    ctl = ref.control(pool, pushes, q)
    assert all(want[p]["num_groups"].max() > 0 for p in pushes)
    assert sum(check.mismatches(ctl[p], want[p]) for p in pushes) > 0


class _Out:
    def __init__(self, emits):
        self.num_groups = np.array([3 if emits else 0, 0])


def test_loop_samples_only_pushes_that_emit():
    loop = manifest.load_module(manifest.BENCH / "loops" / "stream.py")
    calls = []

    def call(*_):
        calls.append(len(calls))
        return _Out(len(calls) % 7 == 0)

    traffic = {"in_flight": 2, "check_sample": 3, "warmup_pushes": 100}
    win = loop.measure(call, [(0,), (1,)], 0.05, traffic,
                       np.random.default_rng(1), False)
    assert win.sample and len(win.sample) <= 3
    for push, out in win.sample:
        assert out.num_groups.max() > 0
        assert (push - 100 + 1) % 7 == 0


def test_int32_bids_exact_past_2_31():
    import jax.numpy as jnp
    cell = manifest.cell(manifest.load(), NAME)
    entry, ref = cell.entry(), cell.reference()
    stream = cell.config["stream"]
    assert (stream["bids_per_ms"], stream["auctions"], stream["bids"],
            stream["in_flight"], stream["cold_span"]) == (
        ref.BIDS_PER_MS, ref.AUCTIONS, ref.BIDS, ref.IN_FLIGHT,
        ref.COLD_SPAN)
    rng = np.random.default_rng(5)
    n = cell.traffic["push_tuples"]
    batch = {"hot": rng.integers(-111, 111, n).astype(np.int32),
             "delay": rng.integers(-27000, 3001, n).astype(np.int32),
             "price": rng.integers(100, 10 ** 8, n).astype(np.int32)}
    for push in (0, 40_000, 400_000):       # 0, 2.6 G and 26 G bids
        assert push * n < 2 ** 31 or push > 0
        g, k, t = entry.nexmark_bids(
            jnp.asarray(push, jnp.int32), jnp.asarray(batch["hot"]),
            jnp.asarray(batch["delay"]), jnp.asarray(batch["price"]), stream)
        ts, auction = ref._bids(batch, push, n)
        np.testing.assert_array_equal(np.asarray(t), ts)
        np.testing.assert_array_equal(np.asarray(g), auction)
        np.testing.assert_array_equal(np.asarray(k), batch["price"])
