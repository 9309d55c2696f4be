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
* ``table_merge`` — the partial rows go into the table by rank: a binary
  search of the sorted table places each row, rows whose key is already
  there are combined in place, and one scatter writes the kept table rows
  and the new rows at their merged positions.  The rows of retired panes
  (every window holding them already emitted) drop out of the same
  scatter.  Rows beyond ``capacity`` are dropped and counted
  (``pane_evictions``): the answers are then no longer exact.
* ``window_emit`` — closed hopping windows: window ``[e - range, e)`` is
  emitted once, by the first push whose watermark ``max_ts -
  max_lateness`` is at or past ``e``, and only if it holds a tuple.  A push
  emits at most ``rows`` windows (:attr:`TableSpec.rows`); later ones wait,
  in order, for the next push.  A window's value for a group is the fold of
  the group's (adjacent) rows in the window's panes, by a few static
  shifts along the table; the groups are compacted to the front of a
  ``capacity``-lane row.
  A push that emits no window skips the stage (``lax.cond``).

Work per push is O(rows of the table + N log N): no ``[C, C]`` mask and no
per-tuple step.  Timestamps are int32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core.combiners import Combiner, get_combiner
from repro.core.eventtime import TS_MIN
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


def _search(group: Array, pane: Array, qg: Array, qp: Array) -> Array:
    """For each query key, the number of table rows whose (group, pane) is
    smaller: a binary search of the sorted table (pad rows sort last)."""
    c = group.shape[0]

    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        at = jnp.minimum(mid, c - 1)
        less = (lo < hi) & _lex_less(group[at], pane[at], qg, qp)
        return (jnp.where(less, mid + 1, lo),
                jnp.where(less | (lo >= hi), hi, mid))

    lo, _ = jax.lax.fori_loop(
        0, c.bit_length(), step,
        (jnp.zeros(qg.shape, jnp.int32), jnp.full(qg.shape, c, jnp.int32)))
    return lo


def _merge(spec: TableSpec, state: TableState, pg, pp, pstates, combiners):
    """The ``table_merge`` stage: the push's partial rows (sorted, pad
    tail) into the table by rank, dropping the rows of retired panes.
    Returns ``(state, rows, evicted)``."""
    c = spec.capacity
    n = pg.shape[0]
    real = pp != PAD_PANE
    pos = _search(state.group, state.pane, pg, pp)
    at = jnp.minimum(pos, c - 1)
    match = real & (state.group[at] == pg) & (state.pane[at] == pp)
    new = real & ~match

    # rows already in the table: fold this push's partial in place
    hit = jnp.where(match, pos, c)
    states = {}
    for comb in combiners:
        old = jax.tree.map(lambda x: x[at], state.states[comb.name])
        merged = comb.partial_merge(old, pstates[comb.name])
        states[comb.name] = jax.tree.map(
            lambda x, m: x.at[hit].set(m, mode="drop", unique_indices=True),
            state.states[comb.name], merged)

    # a table row's place: the rows kept before it, plus the new rows
    # that sort before it; a new row's: the rows kept before its place in
    # the table, plus the new rows before it.  One scatter writes both.
    keep = (state.pane != PAD_PANE) & (state.pane
                                       >= spec.keep_from(state.next_end))
    keep_i = keep.astype(jnp.int32)
    kept_before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(keep_i)])       # [C + 1]
    new_i = new.astype(jnp.int32)
    new_before = jnp.cumsum(
        jnp.zeros((c + 1,), jnp.int32).at[pos].add(new_i))[:c]
    lane = jnp.arange(c, dtype=jnp.int32)
    dest_t = jnp.where(keep, kept_before[:c] + new_before, c + lane)
    rank = jnp.cumsum(new_i) - new_i
    dest_n = jnp.where(new, kept_before[pos] + rank, 2 * c + jnp.arange(n))
    dest = jnp.concatenate([dest_t, dest_n])

    def place(x, y, fill):
        buf = jnp.full((c,), jnp.asarray(fill), x.dtype)
        return buf.at[dest].set(jnp.concatenate([x, y]), mode="drop",
                                unique_indices=True)

    out = {}
    for comb in combiners:
        out[comb.name] = jax.tree.map(
            place, states[comb.name], pstates[comb.name],
            comb.identity((), _key_dtype(state, comb)))
    rows = kept_before[c] + jnp.sum(new_i)
    new_state = state._replace(
        group=place(state.group, pg, PAD_GROUP),
        pane=place(state.pane, pp, PAD_PANE),
        states=out)
    return new_state, jnp.minimum(rows, c), jnp.maximum(rows - c, 0)


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
    ``pane_evictions``, ``pane_rows_hwm``, ``windows_emitted``)."""
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
        state, rows, evicted = _merge(spec, state, pg, pp, pstates,
                                      combiners)
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
