"""Pane-based SWAG: merge networks + swag_panes vs the re-sort oracle.

The pane path must be *element-exact* against sort_pairs_xla +
group_by_aggregate (the re-sort oracle) for every op — incremental ops via
shared per-pane partials, everything else via the bitonic merge of presorted
panes (a fully sorted sequence of a multiset is unique, so the merged window
is bit-identical to the re-sorted one).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import group_by_aggregate, sort_pairs_xla
from repro.core.sorter import bitonic_merge, merge_presorted
from repro.core.swag import (num_windows, pane_compatible, swag, swag_median,
                             swag_panes)
from repro.kernels import common
from conftest import PY_OPS, py_group_aggregate

PANE_OPS = ("sum", "count", "min", "max")


# ---------------------------------------------------------------------------
# merge primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [4, 32, 256])
def test_bitonic_merge_two_halves(n, rng):
    a = np.sort(rng.integers(0, 100, n // 2))
    b = np.sort(rng.integers(0, 100, n // 2))
    x = jnp.array(np.concatenate([a, b]).astype(np.int32))
    (m,) = bitonic_merge((x,), num_keys=1)
    np.testing.assert_array_equal(np.array(m), np.sort(np.concatenate([a, b])))


@pytest.mark.parametrize("run,p", [(8, 2), (16, 4), (32, 8), (64, 1)])
def test_merge_presorted_multiway(run, p, rng):
    runs = [np.sort(rng.integers(0, 1000, run)) for _ in range(p)]
    x = jnp.array(np.concatenate(runs).astype(np.int32))
    (m,) = merge_presorted((x,), run=run, num_keys=1)
    np.testing.assert_array_equal(np.array(m), np.sort(np.concatenate(runs)))


def test_merge_presorted_lexicographic(rng):
    """Two-key merge of (group, key) runs == global lexsort."""
    p, run = 4, 32
    g = rng.integers(0, 5, p * run).astype(np.int32)
    k = rng.integers(0, 50, p * run).astype(np.int32)
    gs, ks = np.empty_like(g), np.empty_like(k)
    for i in range(p):
        sl = slice(i * run, (i + 1) * run)
        o = np.lexsort((k[sl], g[sl]))
        gs[sl], ks[sl] = g[sl][o], k[sl][o]
    mg, mk = merge_presorted((jnp.array(gs), jnp.array(ks)), run=run,
                             num_keys=2)
    o = np.lexsort((k, g))
    np.testing.assert_array_equal(np.array(mg), g[o])
    np.testing.assert_array_equal(np.array(mk), k[o])


@pytest.mark.parametrize("run,p", [(8, 4), (32, 2), (16, 8)])
def test_bitonic_merge_tile_matches_sorter(run, p, rng):
    """Gather-free tile merge == the gather-based sorter merge == np.sort.
    The tile primitives lower only inside a kernel, so the merge runs in an
    interpret-mode ``pallas_call`` over the whole batch."""
    batch = 3
    x = np.stack([np.concatenate(
        [np.sort(rng.integers(0, 999, run)) for _ in range(p)])
        for _ in range(batch)]).astype(np.int32)

    def kernel(x_ref, o_ref):
        (o_ref[...],) = common.bitonic_merge_tile((x_ref[...],), num_keys=1,
                                                  run=run)

    mt = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, jnp.int32), interpret=True)(jnp.array(x))
    for r in range(batch):
        np.testing.assert_array_equal(np.array(mt[r]), np.sort(x[r]))


def test_merge_rejects_bad_shapes():
    with pytest.raises(ValueError):
        merge_presorted((jnp.arange(12),), run=4)
    with pytest.raises(ValueError):
        bitonic_merge((jnp.arange(6),))


# ---------------------------------------------------------------------------
# swag_panes vs the re-sort oracle
# ---------------------------------------------------------------------------

def _oracle_windows(g, k, ws, wa, op):
    outs = []
    for w in range(num_windows(len(g), ws, wa)):
        wg, wk = g[w * wa:w * wa + ws], k[w * wa:w * wa + ws]
        outs.append(py_group_aggregate(wg, wk, PY_OPS[op]))
    return outs


@pytest.mark.parametrize("op", PANE_OPS + ("median",))
@pytest.mark.parametrize("ws,div", [(16, 1), (16, 2), (16, 4), (32, 4)])
@pytest.mark.parametrize("n", [96, 100, 213])  # incl. non-power-of-two
def test_swag_panes_matches_oracle(op, ws, div, n, rng):
    wa = ws // div
    g = rng.integers(0, 6, n).astype(np.int32)
    k = rng.integers(0, 50, n).astype(np.int32)
    res = swag_panes(jnp.array(g), jnp.array(k), ws=ws, wa=wa, op=op,
                     use_xla_sort=True)
    for w, (og, ov) in enumerate(_oracle_windows(g, k, ws, wa, op)):
        nn = int(res.num_groups[w])
        assert nn == len(og)
        np.testing.assert_array_equal(np.array(res[0][w][:nn]), og)
        np.testing.assert_allclose(np.array(res[1][w][:nn], np.float64), ov,
                                   rtol=1e-6)
        assert not np.array(res.valid[w][nn:]).any()


@pytest.mark.parametrize("op", ["mean", "distinct_count", "variance",
                                "first", "last", "argmin", "argmax"])
def test_swag_panes_merge_path_exact_vs_resort(op, rng):
    """Non-incremental ops go through the merge path and must be bit-exact
    against the re-sort path (identical sorted window -> identical engine)."""
    g = jnp.array(rng.integers(0, 5, 128).astype(np.int32))
    k = jnp.array(rng.integers(0, 40, 128).astype(np.int32))
    base = swag(g, k, ws=32, wa=8, op=op, panes=False, use_xla_sort=True)
    pane = swag_panes(g, k, ws=32, wa=8, op=op, use_xla_sort=True)
    for b, p in zip(base, pane):
        np.testing.assert_array_equal(np.array(b), np.array(p))


def test_swag_panes_float_sum_bit_exact(rng):
    """Float sums must stay on the merge path: per-pane partial sums would
    reorder float additions (~ulp drift vs the re-sort path)."""
    g = jnp.array(rng.integers(0, 5, 200).astype(np.int32))
    kf = jnp.array(rng.normal(size=200).astype(np.float32))
    a = swag(g, kf, ws=32, wa=8, op="sum", panes=False, use_xla_sort=True)
    b = swag_panes(g, kf, ws=32, wa=8, op="sum", use_xla_sort=True)
    np.testing.assert_array_equal(np.array(a.values), np.array(b.values))


def test_swag_auto_dispatch_equals_forced_paths(rng):
    """swag(panes=None) == swag(panes=False) == swag_panes for compatible
    (WS, WA); incompatible shapes silently stay on the re-sort path."""
    g = jnp.array(rng.integers(0, 7, 200).astype(np.int32))
    k = jnp.array(rng.integers(0, 99, 200).astype(np.int32))
    auto = swag(g, k, ws=16, wa=4, op="sum", use_xla_sort=True)
    off = swag(g, k, ws=16, wa=4, op="sum", panes=False, use_xla_sort=True)
    for a, b in zip(auto, off):
        np.testing.assert_array_equal(np.array(a), np.array(b))
    # WA not dividing WS -> re-sort path, still correct
    assert not pane_compatible(16, 6)
    res = swag(g, k, ws=16, wa=6, op="sum", use_xla_sort=True)
    for w, (og, ov) in enumerate(_oracle_windows(
            np.array(g), np.array(k), 16, 6, "sum")):
        nn = int(res.num_groups[w])
        np.testing.assert_array_equal(np.array(res.values[w][:nn]), ov)


def test_swag_median_pane_dispatch(rng):
    g = jnp.array(rng.integers(0, 4, 150).astype(np.int32))
    k = jnp.array(rng.integers(0, 100, 150).astype(np.int32))
    auto = swag_median(g, k, ws=32, wa=8, use_xla_sort=True)
    base = swag_median(g, k, ws=32, wa=8, use_xla_sort=True, panes=False)
    np.testing.assert_array_equal(np.array(auto.medians), np.array(base.medians))
    np.testing.assert_array_equal(np.array(auto.num_groups),
                                  np.array(base.num_groups))


def test_swag_panes_network_sorter(rng):
    """The bitonic-network pane sorter (use_xla_sort=False) agrees too."""
    g = jnp.array(rng.integers(0, 6, 80).astype(np.int32))
    k = jnp.array(rng.integers(0, 30, 80).astype(np.int32))
    a = swag_panes(g, k, ws=16, wa=4, op="sum", use_xla_sort=False)
    b = swag_panes(g, k, ws=16, wa=4, op="sum", use_xla_sort=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.array(x), np.array(y))


def test_swag_panes_rejects_incompatible():
    g = jnp.zeros(64, jnp.int32)
    k = jnp.zeros(64, jnp.int32)
    with pytest.raises(ValueError):
        swag_panes(g, k, ws=16, wa=6, op="sum")
    with pytest.raises(ValueError):
        swag_panes(g, k, ws=128, wa=32, op="sum")  # no complete window


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       op=st.sampled_from(PANE_OPS + ("median",)),
       div=st.sampled_from((1, 2, 4)))
def test_property_swag_panes(seed, op, div):
    """Property-style cross-check against the XLA-sort + engine oracle."""
    rng = np.random.default_rng(seed)
    ws = 16
    wa = ws // div
    n = int(rng.integers(ws, 160))
    g = rng.integers(0, int(rng.integers(1, 9)), n).astype(np.int32)
    k = rng.integers(-50, 50, n).astype(np.int32)
    res = swag_panes(jnp.array(g), jnp.array(k), ws=ws, wa=wa, op=op,
                     use_xla_sort=True)
    for w in range(num_windows(n, ws, wa)):
        wg = jnp.array(g[w * wa:w * wa + ws])
        wk = jnp.array(k[w * wa:w * wa + ws])
        if op == "median":
            og, ov = py_group_aggregate(np.array(wg), np.array(wk),
                                        PY_OPS["median"])
            nn = int(res.num_groups[w])
            assert nn == len(og)
            np.testing.assert_array_equal(np.array(res.medians[w][:nn]), ov)
        else:
            sg, sk = sort_pairs_xla(wg, wk)
            want = group_by_aggregate(sg, sk, op)
            nn = int(want.num_groups)
            assert int(res.num_groups[w]) == nn
            np.testing.assert_array_equal(np.array(res.groups[w][:nn]),
                                          np.array(want.groups[:nn]))
            np.testing.assert_array_equal(np.array(res.values[w][:nn]),
                                          np.array(want.values[:nn]))


# ---------------------------------------------------------------------------
# fused pane kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "median"])
def test_swag_tpu_pane_path_forced(op, rng):
    from repro.kernels.swag.ops import swag_tpu
    from repro.kernels.swag.ref import swag_ref

    g = jnp.array(rng.integers(0, 8, 256).astype(np.int32))
    k = jnp.array(rng.integers(0, 50, 256).astype(np.int32))
    got = swag_tpu(g, k, ws=64, wa=16, op=op, panes=True)
    off = swag_tpu(g, k, ws=64, wa=16, op=op, panes=False)
    wg, wv, _, wn = swag_ref(g, k, ws=64, wa=16, op=op)
    np.testing.assert_array_equal(np.array(got.num_groups), np.array(wn))
    for w in range(got.groups.shape[0]):
        nn = int(got.num_groups[w])
        np.testing.assert_array_equal(np.array(got.groups[w, :nn]),
                                      np.array(wg[w, :nn]))
        np.testing.assert_allclose(np.array(got.values[w, :nn], np.float64),
                                   np.array(wv[w, :nn], np.float64),
                                   rtol=1e-6)
    # pane and re-sort kernels agree bit-exactly
    np.testing.assert_array_equal(np.array(got.groups), np.array(off.groups))
    np.testing.assert_array_equal(np.array(got.values), np.array(off.values))
