"""The plain references, on hand-worked cases and against a tiny run of
the system on the CPU; and their controls, which the check must call not
correct."""
from __future__ import annotations

import numpy as np
import pytest

import check
import manifest
from tiny import tiny_cell
from reference import count_window, per_group_window
from reference.layout import PAD_GROUP

OPS = ["min", "max", "sum", "count"]


def _one(module, g, k, q, control=False):
    """The answer to one push of batch ``(g, k)``."""
    f = module.control if control else module.evaluate
    return f([{"groups": g, "keys": k}], [0], q)[0]


def test_count_window_hand_case():
    g = np.array([2, 2, 0, 2, 1, 1], np.int32)
    k = np.array([4, 6, 1, 9, 3, 5], np.int32)
    q = {"ops": OPS, "window": {"ws": 4, "wa": 2}}
    r = _one(count_window, g, k, q)
    # window 0: tuples 0..3, window 1: tuples 2..5
    np.testing.assert_array_equal(
        r["groups"], [[0, 2, PAD_GROUP, PAD_GROUP], [0, 1, 2, PAD_GROUP]])
    np.testing.assert_array_equal(r["num_groups"], [2, 3])
    np.testing.assert_array_equal(r["valid"], [[1, 1, 0, 0], [1, 1, 1, 0]])
    v = r["values"]
    np.testing.assert_array_equal(v["min"][:, :3], [[1, 4, 0], [1, 3, 9]])
    np.testing.assert_array_equal(v["max"][:, :3], [[1, 9, 0], [1, 5, 9]])
    np.testing.assert_array_equal(v["sum"][:, :3], [[1, 19, 0], [1, 8, 9]])
    np.testing.assert_array_equal(v["count"][:, :3], [[1, 3, 0], [1, 2, 1]])
    # the control leaves each window's oldest tuple out
    c = _one(count_window, g, k, q, control=True)
    np.testing.assert_array_equal(c["values"]["count"][:, :3],
                                  [[1, 2, 0], [2, 1, 0]])
    assert check.mismatches(c, r) > 0


def test_per_group_window_hand_case():
    g = np.array([0, 1, 0, 0, 1, 0, 0, 0], np.int32)
    k = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    q = {"ops": OPS, "window": {"ws": 2, "ws_per_group": 2, "wa": 2,
                                "capacity": 4}}
    r = _one(per_group_window, g, k, q)
    # evaluations after 2, 4, 6, 8 tuples; each group's own last 2 tuples
    np.testing.assert_array_equal(r["groups"][:, :2], [[0, 1]] * 4)
    np.testing.assert_array_equal(r["num_groups"], [2, 2, 2, 2])
    v = r["values"]
    np.testing.assert_array_equal(v["min"][:, :2],
                                  [[1, 2], [3, 2], [4, 2], [7, 2]])
    np.testing.assert_array_equal(v["max"][:, :2],
                                  [[1, 2], [4, 2], [6, 5], [8, 5]])
    np.testing.assert_array_equal(v["sum"][:, :2],
                                  [[1, 2], [7, 2], [10, 7], [15, 7]])
    np.testing.assert_array_equal(v["count"][:, :2],
                                  [[1, 1], [2, 1], [2, 2], [2, 2]])
    assert (r["groups"][:, 2:] == PAD_GROUP).all()
    # the control cuts group 0's window at evaluation 1 to its whole pane:
    # of [1, 3, 4] it keeps rank 2 onwards, [4], not the last two [3, 4]
    c = _one(per_group_window, g, k, q, control=True)
    assert c["values"]["sum"][1, 0] == 4
    assert check.mismatches(c, r) > 0


def test_per_group_window_refuses_an_evicting_store():
    g = np.array([0, 1, 2, 3] * 4, np.int32)
    q = {"ops": OPS, "window": {"ws": 8, "ws_per_group": 8, "wa": 4,
                                "capacity": 8}}
    with pytest.raises(ValueError, match="evict"):
        _one(per_group_window, g, g, q)


def test_each_batch_answers_each_push_from_its_batch():
    g0 = np.array([0, 1, 0, 1], np.int32)
    g1 = np.array([1, 1, 1, 1], np.int32)
    k = np.arange(4, dtype=np.int32)
    q = {"ops": OPS, "window": {"ws": 4, "wa": 4}}
    pool = [{"groups": g0, "keys": k}, {"groups": g1, "keys": k}]
    got = count_window.evaluate(pool, [0, 3, 4], q)
    assert set(got) == {0, 3, 4}
    assert got[0] is got[4]
    np.testing.assert_array_equal(got[3]["num_groups"], [1])
    np.testing.assert_array_equal(got[0]["num_groups"], [2])


def _system(cell, g, k, backend):
    import jax.numpy as jnp

    from repro.query import execute
    entry = cell.entry()
    got, _ = execute(entry.build_query(cell.config), jnp.asarray(g),
                     jnp.asarray(k), backend=backend)
    return entry.to_host(got)


def _tiny_batch(cell, seed=5):
    rng = np.random.default_rng(seed)
    n = cell.traffic["push_tuples"]
    cols = {c["name"]: c for c in cell.config["data"]["columns"]}
    g = rng.integers(0, cols["groups"]["high"], n).astype(np.int32)
    k = rng.integers(0, cols["keys"]["high"], n).astype(np.int32)
    return g, k


@pytest.mark.parametrize("name", ["enthuse.flat-swag", "enthuse.per-group"])
@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_reference_matches_the_system(name, backend, kernels_on_cpu):
    """The reference agrees with ``repro.query.execute`` element for
    element at a tiny size, on the ``reference`` backend and on the
    cell's kernel backend (interpret mode); its control does not."""
    cell = tiny_cell(name)
    g, k = _tiny_batch(cell)
    be = (cell.config["kernel_backends"][0] if backend == "kernel"
          else "reference")
    got = _system(cell, g, k, be)
    ref = cell.reference()
    q = cell.config["query"]
    want = _one(ref, g, k, q)
    assert check.mismatches(got, want) == 0
    assert check.mismatches(_one(ref, g, k, q, control=True), want) > 0


@pytest.mark.parametrize("name", ["enthuse.flat-swag", "enthuse.per-group"])
@pytest.mark.parametrize("ops", [["median"], ["distinct_count"],
                                 ["median", "distinct_count", "sum"]])
def test_reference_ops_beyond_the_cells(name, ops):
    """median and distinct_count, which no cell asks for yet, agree with
    the system's ``reference`` backend, so a configuration that asks for
    them needs no new reference."""
    cell = tiny_cell(name)
    cell.config["query"]["ops"] = ops
    g, k = _tiny_batch(cell, seed=11)
    got = _system(cell, g, k, "reference")
    want = _one(cell.reference(), g, k, cell.config["query"])
    assert check.mismatches(got, want) == 0


def test_count_window_without_groups_matches_the_system():
    """``group_by: false`` (no groups column): the stream is group 0."""
    import jax.numpy as jnp

    from repro.query import execute
    cell = tiny_cell("enthuse.flat-swag")
    cell.config["query"]["group_by"] = False
    _, k = _tiny_batch(cell, seed=3)
    entry = cell.entry()
    got, _ = execute(entry.build_query(cell.config), None, jnp.asarray(k),
                     backend="reference")
    want = cell.reference().evaluate([{"keys": k}], [0],
                                     cell.config["query"])[0]
    assert check.mismatches(entry.to_host(got), want) == 0


def test_bytes_per_push_from_shapes():
    """Input read once plus the result written once, from shapes alone."""
    m = manifest.load()
    for w, want in (("enthuse.flat-swag",
                     2 ** 20 * 8 + 4093 * 1024 * 21 + 4093 * 4),
                    ("enthuse.per-group",
                     2 ** 18 * 8 + 2048 * 576 * 21 + 2048 * 4)):
        cell = manifest.cell(m, w)
        ref = cell.reference()
        assert ref.bytes_per_push(cell.config, cell.traffic) == want
