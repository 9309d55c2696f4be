"""A whole run on the CPU (the look for a chip skipped, kernels in
interpret mode) calls itself correct, and calls itself not correct when
the timed path is broken underneath: once for each fault these cells can
have.  (The exchange between chips is not among them: every cell runs on
one chip.)"""
from __future__ import annotations

import time

import pytest

import harness
from tiny import tiny_cell

CELLS = ["enthuse.flat-swag", "enthuse.per-group"]
SEED = 2 ** 31 + 77


def _stale(call):
    """A step that returns its state unchanged: every push answers with
    the previous push's results."""
    last = []

    def f(*cols):
        out = call(*cols)
        last.append(out)
        return last.pop(0) if len(last) > 1 else out
    return f


def _half(call):
    """Half of the batch left out: the second half of every push is
    replaced by the first, so the results cover half the tuples."""
    def f(*cols):
        h = cols[0].shape[0] // 2
        return call(*(c.at[h:].set(c[:h]) for c in cols))
    return f


def _altered(call):
    """One answer altered where it is produced: the first group's sum in
    the first row is one too high."""
    def f(*cols):
        out = call(*cols)
        s = out.values["sum"]
        return out._replace(values=dict(out.values,
                                        sum=s.at[0, 0].add(1)))
    return f


def _run(name, fault=None, trace=False):
    return harness.run(tiny_cell(name), SEED, 0.5, trace,
                       t_process=time.perf_counter(), require_chip=False,
                       fault=fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, kernels_on_cpu):
    line = _run(name)
    assert line["correct"] is True
    assert line["failed"] == 0
    assert line["checks"]["mismatched_elements"] == {"value": 0, "limit": 0}
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert set(line["metrics"]) == {m["name"] for m in
                                    tiny_cell(name).end_to_end}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_stale, _half, _altered])
def test_broken_timed_path_is_not_correct(name, fault, kernels_on_cpu):
    line = _run(name, fault)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert line["checks"]["mismatched_elements"]["value"] > 0


def test_no_chip_no_result():
    """Without a TPU a run raises before it measures anything."""
    with pytest.raises(harness.SetupError, match="TPU"):
        harness.run(tiny_cell(CELLS[0]), SEED, 0.5, False,
                    t_process=time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_a_compile_in_the_window_is_not_correct(name, kernels_on_cpu):
    """A timed call that compiles inside the window fails the run, though
    its results are right."""
    import jax

    def f(call):
        def g(*cols):
            jax.jit(lambda x: x + 1).lower(cols[1]).compile()
            return call(*cols)
        return g
    line = _run(name, f)
    assert line["checks"]["compiles_in_window"]["value"] > 0
    assert line["checks"]["mismatched_elements"]["value"] == 0
    assert line["correct"] is False
