"""Sorted pane-partial table: single-device event-time streaming for the
partial ops (sum, count, min, max, mean).

A deployment that counts, say, bids per auction over hopping windows of
event time needs per-window *aggregates*, not the tuples.  This module keeps
exactly that: one row per ``(group, pane)`` holding each op's combiner
state, in a table of ``capacity`` rows kept sorted by ``(group, pane)``
(empty rows carry the pad key and sit at the end).  A pane is
``ts // pane_width``, with ``pane_width = gcd(range, slide)``, so every
window ``[e - range, e)`` (``e`` a multiple of ``slide``) is a run of whole
panes, and a group's rows are adjacent.  Each push runs three stages
(:mod:`repro.obs.trace` scopes):

* ``time_partials`` — the lateness rule, vectorised: a tuple is late iff
  ``ts < max(max_ts so far, this push's stamps up to and including it) -
  max_lateness`` (a cumulative max, no per-tuple scan); late tuples are
  counted in ``dropped`` and never aggregated.  The push's live tuples are
  sorted by ``(group, pane)`` and each run is reduced (a segment
  reduction of the ops' lifted states): the push's partial rows.
* ``table_merge`` — the partial rows go into the table with no gather or
  scatter.  The table's pad tail takes them, reversed, so its lanes form
  one bitonic sequence; log2(lanes) sweeps of a merge network sort it, with
  the ops' states as payload; a key both sides hold lands on two adjacent
  lanes and folds into the first; that lane's twin and the rows of retired
  panes (every window holding them already emitted) drop out by a
  butterfly compaction.  The short-distance sweeps and rounds run in VMEM
  (:mod:`repro.kernels.tablemerge`).  A push that does not fit the pad tail
  takes a network twice as wide (``table_merge_wide``); rows beyond
  ``capacity`` are dropped and counted (``pane_evictions``): the answers
  are then no longer exact.
* ``window_emit`` — closed hopping windows: window ``[e - range, e)`` is
  emitted once, by the first push whose watermark ``max_ts -
  max_lateness`` is at or past ``e``, and only if it holds a tuple.  A push
  emits at most ``rows`` windows (:attr:`TableSpec.rows`); later ones wait,
  in order, for the next push.  A window's value for a group is the fold of
  the group's (adjacent) rows in the window's panes, by a few static
  shifts along the table; the groups are compacted to the front of a
  ``capacity``-lane row.
  A push that emits no window skips the stage (``lax.cond``).

Work per push is O(C log C + N log N) for a table of ``C`` lanes: no
``[C, C]`` mask and no per-tuple step.  Timestamps are int32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core.combiners import Combiner, get_combiner
from repro.core.eventtime import TS_MIN
from repro.core.sorter import next_pow2
from repro.kernels import common as _kernels
from repro.kernels import tablemerge
from repro.obs.trace import stage

Array = jax.Array

PAD_GROUP = _engine.PAD_GROUP
#: the pane of an empty row: sorts after every real pane
PAD_PANE = jnp.iinfo(jnp.int32).max
#: a window end no real window reaches: ``flush`` emits up to it
TS_END = 2 ** 31 - 1


def _ceil_div(a, b):
    return -((-a) // b)


@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Static configuration of one pane-partial table (hashable).

    ``capacity``: rows of the table, and lanes of each emitted window row.
    ``time_range``/``slide``/``max_lateness``: the hopping window clause in
    the stream's time unit.
    """
    capacity: int
    time_range: int
    slide: int
    max_lateness: int

    def __post_init__(self):
        if self.capacity <= 0:
            raise ValueError(f"capacity must be positive, got "
                             f"{self.capacity}")
        if self.time_range <= 0 or self.slide <= 0:
            raise ValueError(f"range/slide must be positive, got "
                             f"{self.time_range}/{self.slide}")
        if self.max_lateness < 0:
            raise ValueError(f"max_lateness must be >= 0, got "
                             f"{self.max_lateness}")

    @property
    def pane_width(self) -> int:
        """Panes tile both the windows' starts and their ends."""
        return math.gcd(self.time_range, self.slide)

    @property
    def panes_per_window(self) -> int:
        return self.time_range // self.pane_width

    @property
    def rows(self) -> int:
        """Windows one push may emit: as many as hold any one instant, so a
        watermark that advances by up to ``range`` per push never waits."""
        return _ceil_div(self.time_range, self.slide)

    def first_end(self, pane):
        """The first window end whose window holds ``pane``."""
        w, s = self.pane_width, self.slide
        return _ceil_div((pane + 1) * w, s) * s

    def covered(self, pane):
        """Whether any window holds ``pane`` (with ``slide > range`` some
        panes lie in the gaps between windows)."""
        if self.slide <= self.time_range:
            return jnp.ones(jnp.shape(pane), bool)
        return self.first_end(pane) - self.time_range <= pane * self.pane_width

    def keep_from(self, next_end):
        """The first pane still needed once every window ending before
        ``next_end`` has been emitted."""
        return _ceil_div(next_end - self.time_range, self.pane_width)


class TableState(NamedTuple):
    """The streaming carry of the table route (one pytree)."""
    group: Array     # [C] int32, rows sorted by (group, pane); PAD_GROUP empty
    pane: Array      # [C] int32, PAD_PANE on empty rows
    states: dict     # {op name: combiner state, leaves [C]}
    max_ts: Array    # [] int32, the largest stamp seen
    next_end: Array  # [] int32, the next window end to emit (TS_MIN: none)
    dropped: Array   # [] int32, late tuples over the stream's life


class Emission(NamedTuple):
    """The windows one push (or a flush) emits: ``rows`` rows of
    ``capacity`` lanes.  A row that holds no window has ``num_groups`` 0,
    ``ends`` TS_MIN and only pad lanes."""
    groups: Array      # [R, C] int32, ascending, PAD_GROUP tail
    values: dict       # {op name: [R, C]}
    valid: Array       # [R, C] bool
    num_groups: Array  # [R] int32
    ends: Array        # [R] int32, each row's window end


def combiners_of(ops) -> tuple:
    return tuple(op if isinstance(op, Combiner) else get_combiner(op)
                 for op in ops)


def _identity_rows(comb: Combiner, n: int, key_dtype):
    return jax.tree.map(
        lambda fill: jnp.full((n,), jnp.asarray(fill), jnp.asarray(fill).dtype),
        comb.identity((), key_dtype))


def init_table(spec: TableSpec, ops, key_dtype=jnp.int32) -> TableState:
    c = spec.capacity
    return TableState(
        group=jnp.full((c,), PAD_GROUP, jnp.int32),
        pane=jnp.full((c,), PAD_PANE, jnp.int32),
        states={comb.name: _identity_rows(comb, c, key_dtype)
                for comb in combiners_of(ops)},
        max_ts=jnp.asarray(TS_MIN, jnp.int32),
        next_end=jnp.asarray(TS_MIN, jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


def _lex_less(ga, pa, gb, pb):
    return (ga < gb) | ((ga == gb) & (pa < pb))


def _compact(keep: Array, width: int, cols: dict):
    """Scatter the lanes where ``keep`` holds to the front of ``width``
    lanes (stable); lanes past them take each column's fill.  ``cols``
    maps a name to ``(column pytree, fill pytree)``."""
    dest = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, width)

    def one(x, fill):
        buf = jnp.full((width,), jnp.asarray(fill), x.dtype)
        return buf.at[dest].set(x, mode="drop")

    return {k: jax.tree.map(one, x, fill) for k, (x, fill) in cols.items()}


def _key_dtype(state: TableState, comb: Combiner):
    return jax.tree.leaves(state.states[comb.name])[0].dtype


#: how each state leaf of a partial-path op reduces over a run of tuples
_LEAF_REDUCE = {"sum": ("sum",), "count": ("sum",), "mean": ("sum", "sum"),
                "min": ("min",), "max": ("max",)}
_SEGMENT = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
            "max": jax.ops.segment_max}


def _push_partials(spec: TableSpec, state: TableState, groups, keys, ts,
                   live, combiners):
    """The ``time_partials`` stage: lateness, panes, the sort by (group,
    pane) and one partial row per run (a segment reduction, so the rows
    come out compacted to the front of ``N`` lanes).  Returns ``(group,
    pane, states, late, max_ts)``."""
    n = ts.shape[0]
    seen = jnp.where(live, ts, TS_MIN)
    run_max = jnp.maximum(state.max_ts, jax.lax.cummax(seen))
    late = live & (ts < run_max - spec.max_lateness)
    pane = jnp.floor_divide(ts, spec.pane_width)
    ok = live & ~late & spec.covered(pane)
    gk = jnp.where(ok, groups, PAD_GROUP)
    pk = jnp.where(ok, pane, PAD_PANE)
    if all(c.name == "count" for c in combiners):
        gk, pk = jax.lax.sort((gk, pk), num_keys=2)
    else:
        gk, pk, keys = jax.lax.sort((gk, pk, keys), num_keys=2)
    lane = jnp.arange(n)
    starts = (lane == 0) | (gk != jnp.roll(gk, 1)) | (pk != jnp.roll(pk, 1))
    run = jnp.cumsum(starts.astype(jnp.int32)) - 1
    run = jnp.where(pk != PAD_PANE, run, n)        # pad lanes: dropped

    def segment(kind, x):
        return _SEGMENT[kind](x, run, num_segments=n, indices_are_sorted=True)

    states = {}
    for comb in combiners:
        leaves, tree = jax.tree.flatten(comb.lift(keys))
        states[comb.name] = jax.tree.unflatten(tree, [
            segment(kind, x) for kind, x in zip(_LEAF_REDUCE[comb.name],
                                                leaves)])
    first = jnp.where(starts, run, n)
    out_g = jnp.full((n,), PAD_GROUP, jnp.int32).at[first].set(
        gk, mode="drop", unique_indices=True)
    out_p = jnp.full((n,), PAD_PANE, jnp.int32).at[first].set(
        pk, mode="drop", unique_indices=True)
    return (out_g, out_p, states, jnp.sum(late.astype(jnp.int32)),
            run_max[-1])


#: lanes of a row in the 2-D view the merge network works on
_LANES = 128


def _rows_shift(x, k: int, fill):
    """``x`` ([rows, lanes]) moved ``k`` rows towards the end (``k < 0``:
    the start), ``fill`` in the rows left empty."""
    if k == 0:
        return x
    if abs(k) >= x.shape[0]:
        return jnp.full_like(x, fill)
    pad = jnp.full((abs(k), x.shape[1]), jnp.asarray(fill, x.dtype))
    if k > 0:
        return jnp.concatenate([pad, x[:-k]])
    return jnp.concatenate([x[-k:], pad])


def _flat_shift(x, d: int, fill):
    """``x`` ([rows, lanes]) moved ``d`` lanes towards the end of its
    row-major order (``d < 0``: the start), ``fill`` in the lanes left
    empty: a row move for the whole rows, lane rotations for the rest."""
    w = x.shape[1]
    q, s = divmod(abs(d), w)
    sign = 1 if d > 0 else -1
    a = _rows_shift(x, sign * q, fill)
    if s == 0:
        return a
    b = _rows_shift(x, sign * (q + 1), fill)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if d > 0:
        return jnp.where(lane >= s, jnp.roll(a, s, 1), jnp.roll(b, s, 1))
    return jnp.where(lane < w - s, jnp.roll(a, -s, 1), jnp.roll(b, -s, 1))


def _clean_sweep(cols, d: int):
    """One ascending sweep of a bitonic merge over ``cols`` (a pytree of
    [rows, lanes] leaves, keyed by its first two) at a distance of ``d``
    rows: each lane of row ``i`` and the same lane of row ``i ^ d`` swap
    where they are out of order."""
    g, p = cols[:2]
    lower = (jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) & d) == 0
    par = jax.tree.map(lambda x: jnp.where(lower, jnp.roll(x, -d, 0),
                                           jnp.roll(x, d, 0)), cols)
    swap = jnp.where(lower, _lex_less(par[0], par[1], g, p),
                     _lex_less(g, p, par[0], par[1]))
    return jax.tree.map(lambda x, y: jnp.where(swap, y, x), cols, par)


def _butterfly_compact(live, cols, fills, tile: int):
    """The ``live`` lanes of ``cols`` (a pytree of [rows, lanes] leaves)
    moved to the front, in order; the lanes past them take ``fills``.  A
    live lane's displacement (its lane less its rank) never falls along the
    lanes, so moving each by one bit of it a round, lowest bit first, never
    lands two on one lane (``kernels/common.butterfly_compact``).  The
    rounds under ``tile`` rows run in VMEM, the others as row moves, and
    only when some lane has that far to go (fewer dead lanes than a tile
    holds, as after most pushes, leave them nothing to do)."""
    v = live.astype(jnp.int32).reshape(-1)
    rank = (jnp.cumsum(v) - v).reshape(live.shape)
    disp = jnp.where(live, jnp.arange(v.size, dtype=jnp.int32).reshape(
        live.shape) - rank, -1)                      # -1: no row here
    disp, cols = tablemerge.compact_tiles(
        disp, cols, tile, interpret=_kernels.default_interpret())
    span = tile * live.shape[1]

    def row_moves(args):
        disp, cols = args
        b = span
        while b < v.size:
            came = _flat_shift(disp, -b, -1)
            arrive = (came > 0) & ((came & b) != 0)
            stay = (disp >= 0) & ((disp & b) == 0)
            cols = jax.tree.map(
                lambda x, f: jnp.where(arrive, _flat_shift(x, -b, f), x),
                cols, fills)
            disp = jnp.where(arrive, came, jnp.where(stay, disp, -1))
            b *= 2
        return disp, cols

    if span < v.size:
        disp, cols = jax.lax.cond(jnp.max(disp) >= span, row_moves,
                                  lambda args: args, (disp, cols))
    return jax.tree.map(lambda x, f: jnp.where(disp >= 0, x, f), cols, fills)


def _merge_lanes(table, push, fills, combiners, keep_from, width: int):
    """Merge, fold and compact over ``width`` lanes (a power of two): the
    table's columns, pad rows, then the push's columns reversed, so the
    lanes form one bitonic sequence by (group, pane) for ``width``'s log2
    ascending sweeps: those across tiles of ``tablemerge.tile_rows`` rows
    as row rotations, the rest in VMEM (``tablemerge.merge_tiles``).  The
    table's lanes past ``width - n`` must be pads.  ``table``, ``push`` and
    ``fills`` are ``(group, pane, states)``.  Returns the table after the
    push (``capacity`` lanes) and its live rows, which may exceed
    ``capacity``."""
    c, n = table[0].shape[0], push[0].shape[0]
    t = min(c, width - n)
    view = (width // min(width, _LANES), min(width, _LANES))
    cols = jax.tree.map(
        lambda x, y, f: jnp.concatenate([
            x[:t], jnp.full((width - n - t,), f, x.dtype), y[::-1]
        ]).reshape(view), table, push, fills)
    tile = tablemerge.tile_rows(view[0], len(jax.tree.leaves(cols)) + 1)
    d = view[0] // 2
    while d >= tile:
        cols = _clean_sweep(cols, d)
        d //= 2
    cols = tablemerge.merge_tiles(cols, tile,
                                  interpret=_kernels.default_interpret())

    # each side's keys are unique, so a key both hold sits on two adjacent
    # lanes, in either order: the first takes the second's partial and the
    # second dies, as do the rows of retired panes (table rows only: a
    # push's row in a retired pane would be late or in no window)
    g, p, states = cols
    nxt = jax.tree.map(lambda x, f: _flat_shift(x, -1, f), cols, fills)
    real = p != PAD_PANE
    pair = real & (nxt[0] == g) & (nxt[1] == p)
    second = _flat_shift(pair, 1, False)
    for comb in combiners:
        own = states[comb.name]
        merged = comb.partial_merge(own, nxt[2][comb.name])
        states[comb.name] = jax.tree.map(lambda m, x: jnp.where(pair, m, x),
                                         merged, own)
    live = real & ~second & (p >= keep_from)
    cols = _butterfly_compact(live, (g, p, states), fills, tile)
    return (jax.tree.map(lambda x: x.reshape(-1)[:c], cols),
            jnp.sum(live.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _merge(spec: TableSpec, state: TableState, pg, pp, pstates,
           ops: tuple):
    """The ``table_merge`` stage: the push's partial rows (sorted, pad
    tail) into the table, dropping the rows of retired panes, with no
    gather or scatter.  A bitonic network merges the two sorted sides, an
    adjacent fold combines the keys both hold (every op here is
    commutative), a butterfly compacts the dead lanes away.  The network
    spans the table's lanes when the push fits its pad tail, else twice as
    many, which keeps the ``capacity`` smallest keys (the rest are counted
    as evicted).  ``ops`` are op names (static: one compile a shape, also
    when called outside ``jit``).  Returns ``(state, rows, evicted,
    wide)``."""
    combiners = combiners_of(ops)
    c, n = spec.capacity, pg.shape[0]
    width = next_pow2(c)
    fills = (PAD_GROUP, PAD_PANE,
             {comb.name: comb.identity((), _key_dtype(state, comb))
              for comb in combiners})
    table = (state.group, state.pane, state.states)
    push = (pg, pp, pstates)
    keep_from = spec.keep_from(state.next_end)

    def over(lanes):
        return lambda: _merge_lanes(table, push, fills, combiners, keep_from,
                                    lanes)

    if n >= width:
        wide = jnp.asarray(True)
        cols, live = over(next_pow2(width + n))()
    else:
        rows = jnp.sum((state.pane != PAD_PANE).astype(jnp.int32))
        wide = rows + n > width
        cols, live = jax.lax.cond(wide, over(next_pow2(width + n)),
                                  over(width))
    g, p, states = cols
    return (state._replace(group=g, pane=p, states=states),
            jnp.minimum(live, c), jnp.maximum(live - c, 0),
            wide.astype(jnp.int32))


def _window_ends(spec: TableSpec, state: TableState, wm):
    """The ends of the windows this push emits (at most ``rows``, in
    order) and the cursor after them.  The next window with a tuple at or
    after the cursor ``c`` is the first that holds the oldest pane some
    window ending at or after ``c`` holds."""
    c = state.next_end
    ends, emit = [], []
    go = jnp.asarray(True)
    for _ in range(spec.rows):
        q = jnp.min(jnp.where(state.pane >= spec.keep_from(c), state.pane,
                              PAD_PANE))
        e = jnp.maximum(c, spec.first_end(jnp.where(q == PAD_PANE, 0, q)))
        go = go & (q != PAD_PANE) & (e <= wm)
        ends.append(jnp.where(go, e, TS_MIN))
        emit.append(go)
        c = jnp.where(go, e + spec.slide, c)
    return jnp.stack(ends), jnp.stack(emit), c


def _shift(x, d: int, fill):
    """``x`` moved ``d`` lanes towards the end (``d < 0``: the start),
    ``fill`` in the lanes left empty."""
    pad = jnp.full((abs(d),), jnp.asarray(fill, x.dtype))
    if d > 0:
        return jnp.concatenate([pad, x[:-d]])
    return jnp.concatenate([x[-d:], pad])


def _window_row(spec: TableSpec, state: TableState, end, combiners):
    """One window's per-group values.  A group's rows in the window's panes
    are adjacent and at most ``panes_per_window`` of them, so each group's
    last such row folds in the ones before it by a few static shifts; those
    rows are then compacted to the front."""
    c = spec.capacity
    lo = (end - spec.time_range) // spec.pane_width
    inside = (state.pane >= lo) & (state.pane < lo + spec.panes_per_window)
    g = state.group
    cols = {"group": (g, PAD_GROUP)}
    for comb in combiners:
        fill = comb.identity((), _key_dtype(state, comb))
        own = state.states[comb.name]
        acc = own
        for d in range(1, spec.panes_per_window):
            with_d = inside & (_shift(g, d, PAD_GROUP) == g) \
                & _shift(inside, d, False)
            prev = jax.tree.map(lambda x, f: _shift(x, d, f), own, fill)
            merged = comb.partial_merge(prev, acc)
            acc = jax.tree.map(lambda m, a: jnp.where(with_d, m, a),
                               merged, acc)
        cols[comb.name] = (acc, fill)
    # the last in-window row of each group
    keep = inside & ~(_shift(inside, -1, False)
                      & (_shift(g, -1, PAD_GROUP) == g))
    out = _compact(keep, c, cols)
    num = jnp.sum(keep.astype(jnp.int32))
    valid = jnp.arange(c) < num
    values = {}
    for comb in combiners:
        v = comb.finalize(out[comb.name])
        values[comb.name] = jnp.where(valid, v, jnp.zeros((), v.dtype))
    return out["group"], values, valid, num


def _empty_rows(spec: TableSpec, state: TableState, combiners, rows: int):
    c = spec.capacity
    values = {}
    for comb in combiners:
        fill = comb.identity((), _key_dtype(state, comb))
        v = jax.eval_shape(comb.finalize, jax.tree.map(
            lambda f: jax.ShapeDtypeStruct((rows, c), jnp.asarray(f).dtype),
            fill))
        values[comb.name] = jnp.zeros(v.shape, v.dtype)
    return (jnp.full((rows, c), PAD_GROUP, jnp.int32), values,
            jnp.zeros((rows, c), bool), jnp.zeros((rows,), jnp.int32))


def emit(spec: TableSpec, state: TableState, ops, wm):
    """The ``window_emit`` stage: every window the watermark ``wm`` has
    closed, at most ``spec.rows`` of them, oldest first.  Returns
    ``(Emission, state)`` with the cursor advanced past them."""
    combiners = combiners_of(ops)
    with stage("window_emit"):
        ends, go, cursor = _window_ends(spec, state, wm)

        def rows_of(st):
            def one(args):
                e, g = args
                return jax.lax.cond(
                    g, lambda: _window_row(spec, st, e, combiners),
                    lambda: jax.tree.map(lambda x: x[0], _empty_rows(
                        spec, st, combiners, 1)))
            return jax.lax.map(one, (ends, go))

        groups, values, valid, num = jax.lax.cond(
            go[0], rows_of,
            lambda st: _empty_rows(spec, st, combiners, spec.rows), state)
    return (Emission(groups, values, valid, num, ends),
            state._replace(next_end=cursor))


def push(spec: TableSpec, state: TableState, groups: Array, keys: Array,
         ts: Array, ops, *, n_valid: Array | None = None, counters=None):
    """One push: partials, merge, emission (see the module doc).  Returns
    ``(Emission, state)``, or ``(Emission, state, counters)`` with an
    :mod:`repro.obs.counters` dict (``late_dropped``, ``watermark``,
    ``pane_evictions``, ``pane_rows_hwm``, ``table_merge_wide``,
    ``windows_emitted``)."""
    combiners = combiners_of(ops)
    groups = jnp.asarray(groups, jnp.int32)
    ts = jnp.asarray(ts, jnp.int32)
    keys = jnp.asarray(keys)
    n = ts.shape[-1]
    live = (jnp.ones((n,), bool) if n_valid is None
            else jnp.arange(n) < n_valid)
    with stage("time_partials"):
        pg, pp, pstates, late, max_ts = _push_partials(
            spec, state, groups, keys, ts, live, combiners)
    with stage("table_merge"):
        state, rows, evicted, wide = _merge(
            spec, state, pg, pp, pstates, tuple(c.name for c in combiners))
    state = state._replace(max_ts=max_ts, dropped=state.dropped + late)
    wm = max_ts - spec.max_lateness
    out, state = emit(spec, state, ops, wm)
    if counters is None:
        return out, state
    from repro.obs import counters as _c
    counters = _c.put(counters, "late_dropped", state.dropped)
    counters = _c.put(counters, "watermark", wm)
    counters = _c.bump(counters, "pane_evictions", evicted)
    counters = _c.high_water(counters, "pane_rows_hwm", rows)
    counters = _c.bump(counters, "table_merge_wide", wide)
    counters = _c.bump(counters, "windows_emitted",
                       jnp.sum((out.num_groups > 0).astype(jnp.int32)))
    return out, state, counters


def drain(emit_once, cursor, rows: int) -> Emission:
    """Emit every window left: call ``emit_once(cursor) -> (Emission,
    cursor)`` until a call emits fewer than ``rows`` windows, and
    concatenate the calls' rows (a host loop: ``flush`` only)."""
    parts = []
    while True:
        out, cursor = emit_once(cursor)
        parts.append(out)
        if int(jnp.sum((out.num_groups > 0).astype(jnp.int32))) < rows:
            return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


def flush(spec: TableSpec, state: TableState, ops) -> Emission:
    """Every window left that holds a tuple, as if the watermark had passed
    them all."""
    end = jnp.asarray(TS_END, jnp.int32)
    return drain(lambda st: emit(spec, st, ops, end), state, spec.rows)
