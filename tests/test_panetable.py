"""The sorted pane-partial table (``repro.core.panetable``): the
single-device event-time route for sum/count/min/max/mean.

Contracts under test, against the pure-Python oracle of closed hopping
windows (``_window_oracle``): each push emits exactly the windows its
watermark has closed, each once, oldest first, at most ``ceil(R / S)`` a
push (the rest wait); emissions are bit-identical under any
within-lateness shuffle; stragglers beyond the lateness bound are counted
and never aggregated; ``flush`` emits every window left; rows beyond the
table's capacity are counted in ``pane_evictions``; and the route agrees
with the reorder-buffer route on the ops both serve.

The merge stage alone (``_merge``) is held to a plain dict of
``(group, pane) -> state``: matched rows folded, new rows placed, retired
panes dropped, the ``capacity`` smallest keys kept and the rest counted,
on both of its branches; and its compiled program holds no gather and no
scatter.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import panetable as pt
from repro.core.sorter import next_pow2
from repro.core.streaming import StreamingAggregator
from repro.kernels import tablemerge
from repro.obs import counters as _c
from repro.query import (Query, Window, execute, init_stream_state, plan,
                         stream_fn, table_route)

from _window_oracle import ClosedWindows, accept, got_rows

OPS = ("count", "sum", "min", "max", "mean")


def _stream(rng, n, t_max, lateness, n_groups=5, late_share=0.3):
    """In-order stamps, a share of them set back by up to ``lateness``."""
    t = np.sort(rng.integers(0, t_max, n))
    back = rng.integers(0, lateness + 1, n) * (rng.random(n) < late_share)
    t = np.maximum(t - back, 0).astype(np.int32)
    g = rng.integers(0, n_groups, n).astype(np.int32)
    k = rng.integers(-50, 50, n).astype(np.int32)
    return g, k, t


def _run(window, ops, g, k, t, push, *, collect_stats=False):
    p = plan(Query(ops=ops, streaming=True, window=window),
             backend="reference")
    step = jax.jit(stream_fn(p, collect_stats=collect_stats))
    state = init_stream_state(p, jnp.int32, collect_stats=collect_stats)
    out = []
    for i in range(0, g.shape[0], push):
        ports, state = step(g[i:i + push], k[i:i + push], state, None,
                            t[i:i + push])
        out.append(got_rows(ports, ops))
    return p, out, state


def _oracle(window, ops, g, k, t, push):
    pushes = [t[i:i + push] for i in range(0, t.shape[0], push)]
    masks, wms = accept(pushes, window.max_lateness)
    oracle = ClosedWindows(window.range, window.slide,
                           window.table_spec().rows, ops)
    want = []
    for j, (m, wm) in enumerate(zip(masks, wms)):
        sl = slice(j * push, (j + 1) * push)
        want.append(oracle.push(g[sl][m], k[sl][m], t[sl][m], wm))
    return want, oracle


@pytest.mark.parametrize("clause", [(48, 16), (40, 16), (10, 16)],
                         ids=["hopping", "range-not-multiple", "sampling"])
def test_table_matches_closed_window_oracle(rng, clause):
    R, S = clause
    L, push = 24, 32
    g, k, t = _stream(rng, 320, 300, L)
    w = Window(range=R, slide=S, max_lateness=L, capacity=256)
    p, got, state = _run(w, OPS, g, k, t, push)
    assert "pane-partial table" in p.note and table_route(p)
    assert isinstance(state, pt.TableState)
    want, _ = _oracle(w, OPS, g, k, t, push)
    assert got == want
    assert sum(len(x) for x in want) > 3


@pytest.mark.parametrize("seed", [3, 4])
def test_table_emissions_bit_identical_under_shuffle(seed):
    """Any within-push shuffle within the lateness bound leaves every
    push's emission bit-identical (mean included)."""
    rng = np.random.default_rng(seed)
    L, push = 16, 64
    t = np.sort(rng.integers(0, 400, 256)).astype(np.int32)
    g = rng.integers(0, 6, 256).astype(np.int32)
    k = rng.integers(-50, 50, 256).astype(np.int32)
    w = Window(range=60, slide=20, max_lateness=L, capacity=256)
    _, base, st = _run(w, OPS, g, k, t, push)
    order = np.concatenate([
        i + np.argsort(t[i:i + push] + rng.integers(0, L, push),
                       kind="stable") for i in range(0, 256, push)])
    _, shuf, st2 = _run(w, OPS, g[order], k[order], t[order], push)
    assert shuf == base and any(base)
    assert int(st.dropped) == int(st2.dropped) == 0


def test_table_stragglers_counted_not_aggregated():
    w = Window(range=20, slide=10, max_lateness=5, capacity=64)
    g = np.array([0, 0, 0, 0, 1, 0, 0, 0], np.int32)
    k = np.array([1, 2, 3, 4, 1000, 5, 6, 7], np.int32)
    t = np.array([0, 4, 9, 30, 12, 31, 40, 60], np.int32)  # 12 < 30 - 5
    _, got, state = _run(w, ("sum", "count"), g, k, t, 8)
    assert int(state.dropped) == 1
    rows = dict(got[0])
    assert all(1 not in groups for groups in rows.values())
    assert rows == {10: {0: (6, 3)}, 20: {0: (6, 3)}}


def test_table_defers_windows_beyond_rows():
    """A watermark that closes more windows than a push has rows emits
    the oldest ones and leaves the rest, in order, to the next pushes."""
    w = Window(range=20, slide=10, max_lateness=0, capacity=64)
    rows = w.table_spec().rows
    assert rows == 2
    t = np.arange(0, 100, 5, dtype=np.int32)            # 20 stamps to 95
    g = np.zeros(20, np.int32)
    k = np.ones(20, np.int32)
    p = plan(Query(ops=("count",), streaming=True, window=w),
             backend="reference")
    step = jax.jit(stream_fn(p))
    state = init_stream_state(p)
    ports, state = step(g, k, state, None, t)
    assert [e for e, _ in got_rows(ports, ("count",))] == [10, 20]
    z = np.zeros(1, np.int32)
    ends = []
    for _ in range(4):    # pushes that add nothing still drain the backlog
        ports, state = step(z, z, state, jnp.asarray(0), z)
        ends += [e for e, _ in got_rows(ports, ("count",))]
    assert ends == [30, 40, 50, 60, 70, 80, 90]


def test_table_flush_emits_every_window_left(rng):
    L = 12
    g, k, t = _stream(rng, 96, 200, L)
    w = Window(range=30, slide=10, max_lateness=L, capacity=128)
    agg = StreamingAggregator("max", window=w)
    want, oracle = _oracle(w, ("max",), g, k, t, 32)
    for j, i in enumerate(range(0, 96, 32)):
        res = agg.push(g[i:i + 32], k[i:i + 32], timestamps=t[i:i + 32])
        assert got_rows(res, ("max",)) == want[j]
    left = oracle.flush()
    assert left and got_rows(agg.flush(), ("max",)) == left
    # the carry starts over
    res = agg.push(g[:32], k[:32], timestamps=t[:32])
    assert got_rows(res, ("max",)) == want[0]


def test_table_overflow_counted_in_pane_evictions(rng):
    g, k, t = _stream(rng, 128, 200, 0, n_groups=16, late_share=0)
    small = Window(range=40, slide=20, capacity=8)
    _, _, state = _run(small, ("count",), g, k, t, 32, collect_stats=True)
    assert int(state[1]["pane_evictions"]) > 0
    assert int(state[1]["pane_rows_hwm"]) == 8
    assert int(state[1]["table_merge_wide"]) == 4   # 32 rows never fit 8
    roomy = Window(range=40, slide=20, capacity=128)
    _, _, state = _run(roomy, ("count",), g, k, t, 32, collect_stats=True)
    assert int(state[1]["pane_evictions"]) == 0
    assert int(state[1]["table_merge_wide"]) == 0


def test_table_and_reorder_routes_agree_on_count(rng):
    """count alone takes the table; count + median takes the reorder
    buffer.  Over the same stream both emit the same windows, with equal
    count columns."""
    L = 16
    g, k, t = _stream(rng, 256, 300, L)
    w = Window(range=48, slide=16, max_lateness=L, reorder_capacity=128,
               capacity=128)
    p1, one, _ = _run(w, ("count",), g, k, t, 64)
    p2, two, _ = _run(w, ("count", "median"), g, k, t, 64)
    assert "pane-partial table" in p1.note
    assert "reorder buffer" in p2.note
    counts = [[(e, {gi: v[:1] for gi, v in rows.items()}) for e, rows in x]
              for x in two]
    assert one == counts and any(one)


def test_float_sum_keeps_the_reorder_route():
    p = plan(Query(ops=("sum", "min"), streaming=True,
                   window=Window(range=32, slide=8)), backend="reference")
    assert table_route(p, jnp.int32) and not table_route(p, jnp.float32)
    state = init_stream_state(p, jnp.float32)
    assert not isinstance(state, pt.TableState)
    z = np.zeros(8, np.int32)
    res, _ = execute(p, z, np.ones(8, np.float32), timestamps=z)
    assert res.window_ends.shape == (4,)


# ----------------------------------------------------------------- the merge
#: how a dict oracle folds two partial states of each op
_FOLD = {"count": lambda a, b: a + b, "sum": lambda a, b: a + b,
         "min": min, "max": max,
         "mean": lambda a, b: (a[0] + b[0], a[1] + b[1])}
_SPEC = dict(time_range=40, slide=20, max_lateness=0)   # pane width 20


def _rows_of(rng, keys):
    """A random partial state of every op for each key."""
    out = {}
    for key in keys:
        v = [int(x) for x in rng.integers(-50, 50, 3)]
        out[key] = {"count": abs(v[0]), "sum": v[1], "min": v[1],
                    "max": v[2], "mean": (v[1], abs(v[0]))}
    return out


def _columns(rows: dict, lanes: int):
    """``rows`` as sorted (group, pane, states) columns of ``lanes``, pad
    rows (and each op's identity) past them."""
    keys = sorted(rows)
    g = np.full(lanes, pt.PAD_GROUP, np.int32)
    p = np.full(lanes, pt.PAD_PANE, np.int32)
    g[:len(keys)] = [k[0] for k in keys]
    p[:len(keys)] = [k[1] for k in keys]
    states = {}
    for comb in pt.combiners_of(OPS):
        fill = comb.identity((), jnp.int32)
        leaves, tree = jax.tree.flatten(fill)
        cols = [np.full(lanes, np.asarray(f), np.int32) for f in leaves]
        for i, k in enumerate(keys):
            for col, x in zip(cols, jax.tree.leaves(rows[k][comb.name])):
                col[i] = x
        states[comb.name] = jax.tree.unflatten(tree, [jnp.asarray(c)
                                                      for c in cols])
    return jnp.asarray(g), jnp.asarray(p), states


def _table_rows(state: pt.TableState) -> dict:
    g, p = np.asarray(state.group), np.asarray(state.pane)
    live = p != pt.PAD_PANE
    out = {}
    for i in np.flatnonzero(live):
        row = {}
        for name in OPS:
            leaves = [int(np.asarray(x)[i])
                      for x in jax.tree.leaves(state.states[name])]
            row[name] = tuple(leaves) if name == "mean" else leaves[0]
        out[(int(g[i]), int(p[i]))] = row
    assert np.all(np.diff(np.flatnonzero(live)) == 1)          # no holes
    assert not live[live.sum():].any()                          # pad tail
    assert list(out) == sorted(out)                             # sorted
    return out


def _merge_oracle(table: dict, push: dict, keep_from: int, capacity: int):
    """The merge by its rule: rows of retired panes dropped, a key both
    hold folded, the ``capacity`` smallest keys kept, the rest evicted."""
    merged = {k: v for k, v in table.items() if k[1] >= keep_from}
    for k, v in push.items():
        if k in table:
            if k in merged:
                merged[k] = {op: _FOLD[op](merged[k][op], v[op])
                             for op in OPS}
        else:
            merged[k] = v
    keys = sorted(merged)
    return {k: merged[k] for k in keys[:capacity]}, max(len(keys) - capacity,
                                                        0)


_MERGE_CASES = {
    # name: (table rows (< 0: capacity less), push rows, shared keys,
    #        next_end)
    "matched-folded": (30, 12, 8, None),
    "all-new": (30, 12, 0, None),
    "all-pad-push": (30, 0, 0, None),
    "retired-dropped": (40, 10, 5, 80),
    "retire-wave": (-24, 16, 4, 140),      # half the table's panes retire
    "wide": (-7, 12, 6, None),
    "overflow": (-8, 16, 0, None),
}


@functools.lru_cache(maxsize=None)
def _merge_fn(capacity: int, tile: int | None = None):
    """The merge stage jitted for ``capacity``; with ``tile``, its kernels
    take tiles of at most that many rows (so a small table spans several
    and the sweeps and rounds across tiles run too)."""
    spec = pt.TableSpec(capacity=capacity, **_SPEC)

    def merge(*args):
        if tile is None:
            return pt._merge.__wrapped__(spec, *args, OPS)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tablemerge, "tile_rows",
                       lambda rows, ncols: min(rows, tile))
            return pt._merge.__wrapped__(spec, *args, OPS)

    return spec, jax.jit(merge)


@pytest.mark.parametrize("capacity,tile", [(64, None), (56, None), (1024, 2)],
                         ids=["c64", "c56", "c1024-tiles"])
@pytest.mark.parametrize("case", list(_MERGE_CASES))
def test_merge_matches_dict_oracle(case, capacity, tile):
    """One push's partial rows (16 lanes) into the table, against the dict
    rule.  ``wide`` takes the branch over twice the lanes (the push does
    not fit the pad tail); ``overflow`` keeps more rows than the table
    holds; at capacity 56 the network spans 64 lanes; at 1024 the lanes
    span 4 tiles of 256 (8 on the wide branch)."""
    rows, fresh, shared, next_end = _MERGE_CASES[case]
    rng = np.random.default_rng(list(_MERGE_CASES).index(case))
    spec, merge = _merge_fn(capacity, tile)
    n, width = 16, next_pow2(capacity)
    keys = [(g, p) for g in range(max(12, capacity // 8)) for p in range(10)]
    pick = rng.permutation(len(keys))
    rows = capacity + rows if rows < 0 else rows
    old = [keys[i] for i in pick[:rows]]
    lo = 0 if next_end is None else int(spec.keep_from(next_end))
    new = [k for k in (keys[i] for i in pick[rows:]) if k[1] >= lo]
    same = [k for k in old if k[1] >= lo][:shared]
    table = _rows_of(rng, old)
    push = _rows_of(rng, same + new[:fresh - len(same)])
    state = pt.init_table(spec, OPS)
    tg, tp, ts = _columns(table, capacity)
    state = state._replace(group=tg, pane=tp, states=ts)
    if next_end is not None:
        state = state._replace(next_end=jnp.asarray(next_end, jnp.int32))
    out, live, evicted, wide = merge(state, *_columns(push, n))
    want, want_evicted = _merge_oracle(table, push, lo, capacity)
    assert _table_rows(out) == want
    assert (int(live), int(evicted)) == (len(want), want_evicted)
    assert bool(wide) == (rows + n > width)
    assert bool(wide) == (case == "wide" or (case == "overflow"
                                            and width == capacity))
    assert (int(evicted) > 0) == (case == "overflow")


def test_merge_wide_branch_counted():
    """A push that does not fit the table's pad tail takes the wide branch
    and counts it in ``table_merge_wide``; one that fits does not."""
    spec = pt.TableSpec(capacity=64, **_SPEC)
    rng = np.random.default_rng(7)
    keys = [(g, p) for g in range(8) for p in range(7)]
    ops = ("count",)

    @jax.jit
    def step(state, t):
        counters = _c.init(table_merge_wide=jnp.zeros((), jnp.int32))
        _, state, counters = pt.push(spec, state, jnp.zeros(16, jnp.int32),
                                     jnp.ones(16, jnp.int32), t, ops,
                                     counters=counters)
        return state, counters["table_merge_wide"]

    got = []
    for rows in (40, 50):
        state = pt.init_table(spec, ops)
        g, p, _ = _columns(_rows_of(rng, keys[:rows]), 64)
        state = state._replace(group=g, pane=p)
        _, wide = step(state, jnp.full(16, 1000, jnp.int32))
        got.append(int(wide))
    assert got == [0, 1]


def test_merge_holds_no_gather_or_scatter():
    """The merge stage reads no lane at a computed address: its program
    (lowered and compiled) has no gather, no scatter and no sort."""
    spec, merge = _merge_fn(64)
    state = pt.init_table(spec, OPS)
    g, p, s = _columns({}, 16)
    lowered = merge.lower(state, g, p, s)
    ops = "gather|scatter|sort"
    assert not re.findall(rf"stablehlo\.({ops})\b", lowered.as_text())
    assert not re.findall(rf"\s({ops})\(", lowered.compile().as_text())
    assert re.findall(r"\sselect\(", lowered.compile().as_text())
