"""Per-group pane store — the paper's approximation for SWAG with per-group
windows.

The headline functionality claim of the paper is SWAG *with groups* at up to
4x the window sizes of the state of the art, achieved by **approximating
per-group windows**: instead of per-group hash state sized for the worst
case, keep only the last ``WS_g`` tuples *per group* in a shared on-chip pane
store and replay each group's pane subset through the merge network — no
DRAM, no per-group hash state.  This module is that store as a static-shape
JAX subsystem:

  * a fixed-capacity ring of ``capacity`` pane slots, each holding up to
    ``WA`` tuples of **one** group (struct-of-arrays; the shared on-chip
    buffer of Gulisano et al.'s multiway-aggregation ADTs — one budget, no
    spill);
  * a **per-group pane index**: a group's slots are found by their ``owner``
    tag and ordered by ``base`` (the within-group sequence number of the
    slot's first tuple) — group id -> its last ``ceil(WS_g/WA)`` (+1
    straddling) pane slots, recovered by one sort of the slot directory;
  * panes are **sorted once**, at close time (when the WA-th tuple arrives),
    so replay merges presorted runs instead of re-sorting — the amortisation
    argument of the pane-based SWAG layer (PR 1) carried over to per-group
    windows;
  * **retirement + eviction**: a slot is *retired* (freed) the moment none
    of its tuples can fall in its group's last ``WS_g`` (worst-case constant
    bookkeeping per push, in the spirit of Tangwongsan et al.'s in-order
    SWAG); when an allocation finds no free slot the globally **oldest**
    pane (smallest allocation stamp) is evicted — the victim group's
    effective window shrinks, which is the paper's approximation knob;
  * **replay**: gather a group's pane subset, feed it through the existing
    bitonic merge network (``sorter.merge_presorted``) with a per-lane
    liveness mask, compact, and apply every requested operator to the one
    merged window (element-exact for sum/count/min/max/median/mean/dc;
    engine-tail fallback — exact vs a full re-sort — otherwise).

Because each tuple carries its within-group sequence number (``seq``)
through the pane sort as payload, the replayed window is the group's last
``WS_g`` tuples *exactly* (not pane-quantised): lanes with
``seq < m_g - WS_g`` stay in their sorted position but are masked dead, so
closed panes remain presorted runs for the merge network.

The streaming carry of ``Query(..., window=..., streaming=True)`` *is* a
:class:`PaneStoreState`; the batch entry (:func:`repro.core.swag.
swag_per_group`) threads it over ``WA``-sized stream chunks and emits one
replay per chunk.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core import sorter
from repro.core.combiners import Combiner, get_combiner

Array = jax.Array

PAD_GROUP = _engine.PAD_GROUP

#: ops the replay tail computes directly from the merged, compacted window
#: (element-exact vs the naive keep-last-WS_g reference)
DIRECT_OPS = frozenset(
    {"sum", "count", "min", "max", "mean", "median", "distinct_count"})

#: ops the **per-pane partial fast path** serves without tuple replay: their
#: window value is a function of per-pane partial aggregates (set-based, so
#: the pane sort order is irrelevant), so evaluation never touches the merge
#: network.  median/distinct_count stay on the merge-replay path — they need
#: the full sorted window.
PANE_PARTIAL_OPS = frozenset({"sum", "count", "min", "max", "mean"})


def partial_path_names(names, key_dtype) -> list:
    """Which ops ride the per-pane partial fast path (True) vs merge-replay
    (False) for this key dtype — the per-group mirror of
    :func:`repro.core.swag.pane_table_channel`'s predicate.

    Float sums (and mean, which divides one) combine per-pane partials in a
    different order than the merged-window reduction, so on float keys they
    stay on the merge path (bit-exactness over ~ulp drift); float
    min/max/count are order-invariant and keep the fast path."""
    reorder_sensitive = jnp.issubdtype(jnp.dtype(key_dtype), jnp.floating)
    return [isinstance(nm, str) and nm in PANE_PARTIAL_OPS
            and not (reorder_sensitive and nm in ("sum", "mean"))
            for nm in names]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


#: "no retirement" floor for time-mode pushes (mirrors
#: ``repro.core.eventtime.TS_MIN``)
TS_FLOOR = -(2 ** 30)


@dataclasses.dataclass(frozen=True)
class PaneStoreSpec:
    """Static configuration of one pane store (hashable; jit-static).

    ``wa``: pane width (power of two — the merge network's wiring
    constraint).  ``capacity``: number of pane slots in the shared buffer.
    ``default_ws``: window size for groups not listed in ``per_group``.
    ``per_group``: sorted tuple of ``(group_id, ws)`` overrides.

    **Time mode** (``slide``/``time_range`` both set — the event-time layer
    of ``repro.core.eventtime``): pane identity becomes the *time pane*
    ``ts // slide`` instead of the within-group tuple count, each tuple's
    timestamp rides through the pane sort as the ``seqs`` payload, and
    panes retire by **watermark advance** (a pane is freed once its whole
    time interval falls behind ``retire_below = watermark - time_range``)
    rather than by tuple count.  ``wa`` then bounds the tuples one slot
    holds of one (group, time-pane); denser traffic chains extra slots
    with the same pane id.  Time mode is per-group-window-free
    (``per_group`` must be empty): every group's window is the shared time
    range ``[eval_time - time_range, eval_time)``.
    """
    wa: int
    capacity: int
    default_ws: int
    per_group: tuple = ()
    slide: int | None = None
    time_range: int | None = None

    def __post_init__(self):
        if self.wa <= 0 or self.wa & (self.wa - 1):
            raise ValueError(f"pane width wa must be a positive power of "
                             f"two, got {self.wa}")
        if self.default_ws <= 0:
            raise ValueError(f"default_ws must be positive, got "
                             f"{self.default_ws}")
        if (self.slide is None) != (self.time_range is None):
            raise ValueError("slide and time_range come together (time "
                             "mode) or not at all (count mode)")
        if self.slide is not None:
            if self.slide <= 0 or self.time_range <= 0:
                raise ValueError(f"slide/time_range must be positive, got "
                                 f"{self.slide}/{self.time_range}")
            if self.per_group:
                raise ValueError("time-mode stores share one time range — "
                                 "per_group window overrides do not apply")
        pairs = tuple(sorted((int(g), int(w)) for g, w in self.per_group))
        for g, w in pairs:
            if w <= 0:
                raise ValueError(f"ws_per_group[{g}] must be positive, "
                                 f"got {w}")
        object.__setattr__(self, "per_group", pairs)
        if self.capacity < self.min_capacity:
            raise ValueError(
                f"capacity={self.capacity} cannot hold even one group's "
                f"window (need >= {self.min_capacity} slots)")

    @property
    def is_time(self) -> bool:
        return self.slide is not None

    @property
    def max_ws(self) -> int:
        return max([self.default_ws] + [w for _, w in self.per_group])

    @property
    def max_panes(self) -> int:
        """Most slots one group can hold: ceil(WS_g/WA) full panes plus one
        straddling the window's trailing edge.  Time mode: slot chaining
        (more than ``wa`` tuples per slide interval) means one group may in
        the worst case own *every* slot, so the replay width must cover the
        whole directory."""
        if self.is_time:
            return self.capacity
        return _ceil_div(self.max_ws, self.wa) + 1

    @property
    def min_capacity(self) -> int:
        if self.is_time:
            return _ceil_div(self.time_range, self.slide) + 1
        return self.max_panes

    @property
    def runs(self) -> int:
        """Replay width in runs: max_panes padded to a power of two (the
        multiway merge needs a power-of-two run count)."""
        return sorter.next_pow2(self.max_panes)

    def ws_of(self, gids: Array) -> Array:
        """Vectorised per-group window-size lookup (the pane index's only
        per-group metadata; the dict is static, so this is a handful of
        compares, not hash state)."""
        ws = jnp.full(jnp.shape(gids), self.default_ws, jnp.int32)
        for g, w in self.per_group:
            ws = jnp.where(gids == g, w, ws)
        return ws


def default_capacity(wa: int, default_ws: int, per_group: tuple = ()) -> int:
    """Heuristic capacity: room for every listed group's window plus four
    default-window groups, rounded up to a power of two (min 16)."""
    need = sum(_ceil_div(w, wa) + 1 for _, w in per_group)
    need += 4 * (_ceil_div(default_ws, wa) + 1)
    return sorter.next_pow2(max(16, need))


class PaneStoreState(NamedTuple):
    """The shared, evicting pane buffer (one pytree — the streaming carry).

    Slot ``i`` holds up to ``WA`` tuples of group ``owner[i]``
    (``PAD_GROUP`` marks a free slot).  ``keys`` are arrival-ordered while
    the pane is open and (key-)sorted once it closes; ``seqs`` carries each
    tuple's within-group sequence number through the sort as payload.
    ``base`` is the seq of the slot's first tuple (the per-group pane
    index's ordering key); ``stamp`` is the global allocation counter value
    (the eviction order); ``clock`` is the next stamp.
    """
    owner: Array   # [C] int32
    keys: Array    # [C, WA]
    seqs: Array    # [C, WA] int32
    count: Array   # [C] int32
    base: Array    # [C] int32
    stamp: Array   # [C] int32
    clock: Array   # [] int32


def init_store(spec: PaneStoreSpec, key_dtype=jnp.int32) -> PaneStoreState:
    c, wa = spec.capacity, spec.wa
    return PaneStoreState(
        owner=jnp.full((c,), PAD_GROUP, jnp.int32),
        keys=jnp.zeros((c, wa), key_dtype),
        seqs=jnp.zeros((c, wa), jnp.int32),
        count=jnp.zeros((c,), jnp.int32),
        base=jnp.zeros((c,), jnp.int32),
        stamp=jnp.full((c,), -1, jnp.int32),
        clock=jnp.zeros((), jnp.int32),
    )


def _push_decide(spec: PaneStoreSpec, owner: Array, count: Array,
                 base: Array, stamp: Array, clock: Array, g: Array, live):
    """The directory half of one push: slot choice, count/owner/base/stamp
    bookkeeping, retirement and eviction — everything about absorbing one
    tuple that never reads the ``[C, WA]`` ring buffers.  Shared by
    :func:`_push_one` (full state) and the batched evaluation path's
    directory-only scan (``repro.core.swag``), so placement policy cannot
    drift between them.

    Returns ``((owner, count, base, stamp, clock), slot, lane, m_g, alloc,
    closes, evicted)``: the updated directory columns, the written slot and
    lane, the new tuple's within-group seq, whether a fresh slot was
    allocated, whether the write closed the pane (the sort trigger), and
    whether the allocation evicted a live pane.
    """
    wa = spec.wa

    mine = owner == g
    any_mine = jnp.any(mine)
    # the index: the group's newest slot is its max-base slot
    newest = jnp.argmax(jnp.where(mine, base, -1))
    m_g = jnp.where(any_mine, base[newest] + count[newest],
                    jnp.zeros((), jnp.int32))
    has_open = any_mine & (count[newest] < wa)

    # allocation target when no open pane: first free slot, else evict the
    # globally oldest pane (min stamp) — the approximation knob
    free = owner == PAD_GROUP
    any_free = jnp.any(free)
    imax = jnp.iinfo(jnp.int32).max
    oldest = jnp.argmin(jnp.where(free, imax, stamp))
    slot = jnp.where(has_open, newest,
                     jnp.where(any_free, jnp.argmax(free), oldest))
    lane = jnp.where(has_open, count[slot], 0)

    alloc = live & ~has_open
    new_count = count.at[slot].set(
        jnp.where(live, jnp.where(has_open, count[slot] + 1, 1),
                  count[slot]))
    new_owner = owner.at[slot].set(jnp.where(alloc, g, owner[slot]))
    new_base = base.at[slot].set(jnp.where(alloc, m_g, base[slot]))
    new_stamp = stamp.at[slot].set(jnp.where(alloc, clock, stamp[slot]))
    new_clock = clock + alloc.astype(jnp.int32)

    # the close test reads the pre-retirement count: a pane that closes and
    # instantly retires (ws_g < wa) still sorts first — state bit-exactness
    # vs the historical single-step update demands the same order
    closes = live & (new_count[slot] == wa)

    # retire this group's panes that no longer intersect its last WS_g
    ws_g = spec.ws_of(g)
    m_new = m_g + 1
    dead = live & (new_owner == g) & (new_base + wa <= m_new - ws_g)
    new_owner = jnp.where(dead, PAD_GROUP, new_owner)
    new_count = jnp.where(dead, 0, new_count)
    new_stamp = jnp.where(dead, -1, new_stamp)

    evicted = live & ~has_open & ~any_free
    return ((new_owner, new_count, new_base, new_stamp, new_clock),
            slot, lane, m_g, alloc, closes, evicted)


def _push_one(spec: PaneStoreSpec, st: PaneStoreState, g: Array, k: Array,
              live: Array, counters=None):
    """Absorb one tuple (no-op when ``live`` is False) — the store's unit of
    worst-case-constant work: locate the open pane via the index, append,
    sort-on-close, retire dead panes, evict the globally oldest on overflow.

    O(C + WA) per step: the directory update (:func:`_push_decide`), one
    dynamic lane write, and a close-time row sort under ``lax.cond`` —
    never a ``[C, WA]`` broadcast (the full-buffer rewrite per tuple was
    the per-group throughput cliff).

    With ``counters`` (an :mod:`repro.obs.counters` dict) returns
    ``(state, counters)`` recording evictions and the occupancy high-water
    mark; ``None`` (the default) traces exactly the pre-observability ops.
    """
    g = g.astype(jnp.int32)
    (owner, count, base, stamp, clock), slot, lane, m_g, _alloc, closes, \
        evicted = _push_decide(spec, st.owner, st.count, st.base, st.stamp,
                               st.clock, g, live)

    keys = st.keys.at[slot, lane].set(
        jnp.where(live, k, st.keys[slot, lane]))
    seqs = st.seqs.at[slot, lane].set(
        jnp.where(live, m_g, st.seqs[slot, lane]))

    # sort the pane once, the moment it closes (seq rides as payload)
    def _sort_row(ks):
        kk, ss = ks
        order = jnp.argsort(kk[slot], stable=True)
        return (kk.at[slot].set(kk[slot][order]),
                ss.at[slot].set(ss[slot][order]))

    keys, seqs = jax.lax.cond(closes, _sort_row, lambda ks: ks, (keys, seqs))

    new_state = PaneStoreState(owner, keys, seqs, count, base, stamp, clock)
    if counters is None:
        return new_state
    from repro.obs import counters as _c
    counters = _c.bump(counters, "pane_evictions", evicted.astype(jnp.int32))
    counters = _c.high_water(counters, "pane_occupancy_hwm",
                             jnp.sum((owner != PAD_GROUP).astype(jnp.int32)))
    return new_state, counters


def push(spec: PaneStoreSpec, state: PaneStoreState, groups: Array,
         keys: Array, n_valid: Array | None = None, counters=None):
    """Stream one batch of tuples through the store (a ``lax.scan`` of the
    constant-work single-tuple step — the software rendering of the
    hardware's one-tuple-per-cycle ingest).

    With ``counters`` returns ``(state, counters)``; the counters ride the
    scan carry, so eviction counts and the occupancy high-water mark cover
    every intermediate cycle, not just the batch boundary."""
    groups = jnp.asarray(groups, jnp.int32)
    keys = jnp.asarray(keys, state.keys.dtype)
    n = groups.shape[-1]
    live = jnp.ones((n,), bool) if n_valid is None else jnp.arange(n) < n_valid

    if counters is None:
        def step(st, x):
            g, k, lv = x
            return _push_one(spec, st, g, k, lv), None

        state, _ = jax.lax.scan(step, state, (groups, keys, live))
        return state

    from repro.obs import counters as _c
    counters = _c.ensure(counters, ("pane_evictions", "pane_occupancy_hwm"))

    def step(carry, x):
        st, cnt = carry
        g, k, lv = x
        return _push_one(spec, st, g, k, lv, counters=cnt), None

    (state, counters), _ = jax.lax.scan(step, (state, counters),
                                        (groups, keys, live))
    return state, counters


def _push_one_time(spec: PaneStoreSpec, st: PaneStoreState, g: Array,
                   k: Array, t: Array, lv: Array,
                   retire_below: Array, counters=None):
    """Absorb one timestamped tuple (time mode).  Pane identity is the time
    pane ``t // slide`` (stored in ``base``); the timestamp rides the pane
    sort as the ``seqs`` payload; a pane whose whole interval has fallen
    behind ``retire_below`` is freed (watermark-driven retirement).  A
    ``(group, pane)`` denser than ``wa`` tuples chains a fresh slot with
    the same pane id.  Same worst-case-constant work per cycle as
    :func:`_push_one`."""
    wa = spec.wa
    g = g.astype(jnp.int32)
    t = t.astype(jnp.int32)
    pid = jnp.floor_divide(t, spec.slide)

    # the index: this tuple's open pane is the (owner, pane-id) slot with
    # room left — at most one exists (a chain's earlier links are full)
    mine_open = (st.owner == g) & (st.base == pid) & (st.count < wa)
    has_open = jnp.any(mine_open)

    free = st.owner == PAD_GROUP
    any_free = jnp.any(free)
    imax = jnp.iinfo(jnp.int32).max
    oldest = jnp.argmin(jnp.where(free, imax, st.stamp))
    slot = jnp.where(has_open, jnp.argmax(mine_open),
                     jnp.where(any_free, jnp.argmax(free), oldest))

    lane = jnp.where(has_open, st.count[slot], 0)
    alloc = lv & ~has_open
    new_count = st.count.at[slot].set(
        jnp.where(lv, jnp.where(has_open, st.count[slot] + 1, 1),
                  st.count[slot]))
    new_owner = st.owner.at[slot].set(jnp.where(alloc, g, st.owner[slot]))
    new_base = st.base.at[slot].set(jnp.where(alloc, pid, st.base[slot]))
    new_stamp = st.stamp.at[slot].set(
        jnp.where(alloc, st.clock, st.stamp[slot]))
    clock = st.clock + alloc.astype(jnp.int32)

    new_keys = st.keys.at[slot, lane].set(
        jnp.where(lv, k, st.keys[slot, lane]))
    new_seqs = st.seqs.at[slot, lane].set(
        jnp.where(lv, t, st.seqs[slot, lane]))

    # sort the pane once, the moment it closes (timestamp rides as payload)
    closes = lv & (new_count[slot] == wa)

    def _sort_row(ks):
        kk, ss = ks
        order = jnp.argsort(kk[slot], stable=True)
        return (kk.at[slot].set(kk[slot][order]),
                ss.at[slot].set(ss[slot][order]))

    new_keys, new_seqs = jax.lax.cond(closes, _sort_row, lambda ks: ks,
                                      (new_keys, new_seqs))

    # watermark-driven retirement: the pane [base*slide, (base+1)*slide)
    # can never again intersect a window once it is wholly below the horizon
    occ = new_owner != PAD_GROUP
    dead = occ & ((new_base + 1) * spec.slide <= retire_below)
    new_owner = jnp.where(dead, PAD_GROUP, new_owner)
    new_count = jnp.where(dead, 0, new_count)
    new_stamp = jnp.where(dead, -1, new_stamp)

    new_state = PaneStoreState(new_owner, new_keys, new_seqs, new_count,
                               new_base, new_stamp, clock)
    if counters is None:
        return new_state
    from repro.obs import counters as _c
    evicted = lv & ~has_open & ~any_free
    counters = _c.bump(counters, "pane_evictions", evicted.astype(jnp.int32))
    counters = _c.high_water(counters, "pane_occupancy_hwm",
                             jnp.sum((new_owner != PAD_GROUP)
                                     .astype(jnp.int32)))
    return new_state, counters


def push_time(spec: PaneStoreSpec, state: PaneStoreState, groups: Array,
              keys: Array, ts: Array, live: Array | None = None,
              retire_below: Array | None = None, counters=None):
    """Stream one batch of timestamped tuples through a time-mode store.

    ``live`` is a full per-lane mask (reorder-buffer emissions are not a
    valid prefix); ``retire_below`` the retirement horizon, normally
    ``watermark - time_range`` (``None`` retires nothing).  With
    ``counters`` returns ``(state, counters)`` (see :func:`push`).
    """
    if not spec.is_time:
        raise ValueError("push_time needs a time-mode PaneStoreSpec "
                         "(slide/time_range set); use push() for "
                         "count-based panes")
    groups = jnp.asarray(groups, jnp.int32)
    keys = jnp.asarray(keys, state.keys.dtype)
    ts = jnp.asarray(ts, jnp.int32)
    n = groups.shape[-1]
    lv = (jnp.ones((n,), bool) if live is None
          else jnp.asarray(live, bool))
    rb = (jnp.full((), TS_FLOOR, jnp.int32) if retire_below is None
          else jnp.asarray(retire_below, jnp.int32))

    if counters is None:
        def step(st, x):
            g, k, t, v = x
            return _push_one_time(spec, st, g, k, t, v, rb), None

        state, _ = jax.lax.scan(step, state, (groups, keys, ts, lv))
        return state

    from repro.obs import counters as _c
    counters = _c.ensure(counters, ("pane_evictions", "pane_occupancy_hwm"))

    def step(carry, x):
        st, cnt = carry
        g, k, t, v = x
        return _push_one_time(spec, st, g, k, t, v, rb, counters=cnt), None

    (state, counters), _ = jax.lax.scan(step, (state, counters),
                                        (groups, keys, ts, lv))
    return state, counters


class ReplayRuns(NamedTuple):
    """One gathered replay snapshot: per output row (candidate group), its
    pane subset flattened to ``runs * WA`` lanes of presorted runs.
    ``run_valid`` already folds slot occupancy, open-pane fill *and*
    staleness (``seq < m_g - WS_g``), so downstream consumers (reference
    merge or the Pallas kernel) need no further per-group metadata."""
    groups: Array      # [C] int32 unique live group ids, ascending, PAD tail
    run_keys: Array    # [C, runs*WA] — each WA-run key-sorted ascending
    run_valid: Array   # [C, runs*WA] bool — live lanes
    num_groups: Array  # [] int32


def _slot_directory(state: PaneStoreState):
    """The per-group pane index, materialised once per evaluation: sort the
    slot directory by (owner, base) and dedupe owners.  Returns ``(perm,
    ugroups, offsets, nslots, num, n_occ)`` — the (owner, base)-sorted slot
    permutation, the unique live group ids (ascending, PAD tail), each
    group's first position in ``perm`` and its slot count, the live-group
    count and the occupied-slot count."""
    c = state.owner.shape[0]
    so, _sb, perm = jax.lax.sort(
        (state.owner, state.base, jnp.arange(c, dtype=jnp.int32)),
        num_keys=2)
    occupied = so != PAD_GROUP
    prev = jnp.concatenate([jnp.full((1,), PAD_GROUP, jnp.int32), so[:-1]])
    firsts = occupied & ((so != prev) | (jnp.arange(c) == 0))
    num = jnp.sum(firsts.astype(jnp.int32))

    rank = jnp.cumsum(firsts.astype(jnp.int32)) - firsts.astype(jnp.int32)
    scatter = jnp.where(firsts, rank, c)
    ugroups = jnp.full((c + 1,), PAD_GROUP, jnp.int32).at[scatter].set(
        so, mode="drop")[:c]
    offsets = jnp.full((c + 1,), c, jnp.int32).at[scatter].set(
        jnp.arange(c, dtype=jnp.int32), mode="drop")[:c]
    n_occ = jnp.sum(occupied.astype(jnp.int32))
    next_off = jnp.concatenate([offsets[1:], jnp.full((1,), c, jnp.int32)])
    nslots = jnp.where(jnp.arange(c) < num,
                       jnp.minimum(next_off, n_occ) - offsets, 0)
    return perm, ugroups, offsets, nslots, num, n_occ


def _slot_sorted(spec: PaneStoreSpec, state: PaneStoreState):
    """Per-slot replay view of the ring buffers: every *closed* pane is
    already key-sorted (sorted once at close); open panes get their dead
    lanes pushed to the tail and sorted here — once per **slot**, instead of
    once per replay row (the per-row sort repeated each open pane's work
    ``S`` times).  Returns ``(keys, seqs, filled)``, each ``[C, WA]``, with
    every row a presorted ascending run."""
    wa = spec.wa
    sentinel = _key_sentinel(state.keys.dtype)
    lanes = jnp.arange(wa)[None, :]
    filled = lanes < state.count[:, None]
    sk = jnp.where(filled, state.keys, sentinel)
    order = jnp.argsort(sk, axis=-1, stable=True)
    srt_k = jnp.take_along_axis(sk, order, axis=-1)
    srt_s = jnp.take_along_axis(state.seqs, order, axis=-1)
    srt_f = jnp.take_along_axis(filled, order, axis=-1)
    is_sorted = (state.count == wa)[:, None]    # closed => sorted once
    return (jnp.where(is_sorted, state.keys, srt_k),
            jnp.where(is_sorted, state.seqs, srt_s),
            jnp.where(is_sorted, filled, srt_f))


def gather_runs(spec: PaneStoreSpec, state: PaneStoreState,
                eval_time: Array | None = None) -> ReplayRuns:
    """The per-group pane index, applied: order the slot directory by
    (owner, base), dedupe owners, and hand each group its (static-width)
    pane subset as presorted runs with a liveness mask.

    Open panes (arrival-ordered) are sorted at the slot level
    (:func:`_slot_sorted`) — every *closed* pane was sorted exactly once at
    close, so the sort-once amortisation holds.  A padded row (slot index
    past the group's count) may gather another slot's real keys rather than
    sentinels; its ``slot_ok`` mask is False, every run is still ascending,
    and the merge + compaction outputs depend only on the live lanes, so
    the replayed window is unchanged.

    Time mode takes ``eval_time`` and masks by the stored timestamps: a
    lane is live iff its tuple falls in ``[eval_time - time_range,
    eval_time)`` (every group shares the one time window, so no per-group
    ``m_g``/``WS_g`` bookkeeping applies).
    """
    c, wa = spec.capacity, spec.wa
    s = spec.runs
    if spec.is_time:
        if eval_time is None:
            raise ValueError("time-mode stores gather against a watermark: "
                             "pass eval_time=")
        et = jnp.asarray(eval_time, jnp.int32)
    elif eval_time is not None:
        raise ValueError("eval_time only applies to time-mode stores")

    perm, ugroups, offsets, nslots, num, _n_occ = _slot_directory(state)
    keys_v, seqs_v, filled_v = _slot_sorted(spec, state)

    def row(r):
        g = ugroups[r]
        o, ns = offsets[r], nslots[r]
        j = jnp.arange(s)
        sidx = perm[jnp.clip(o + j, 0, c - 1)]
        slot_ok = j < ns
        rk = keys_v[sidx]                          # [S, WA]
        rs = seqs_v[sidx]
        filled = filled_v[sidx]

        if spec.is_time:
            # rs holds timestamps: live iff in the evaluation window
            lane_ok = (slot_ok[:, None] & filled &
                       (rs >= et - spec.time_range) & (rs < et))
        else:
            # rs holds within-group seqs: newest slot is the last occupied
            # one (base-ascending order); stale lanes masked dead
            rb = state.base[sidx]
            rc = jnp.where(slot_ok, state.count[sidx], 0)
            last = jnp.clip(ns - 1, 0, s - 1)
            m_g = jnp.where(ns > 0, rb[last] + rc[last], 0)
            lo = m_g - spec.ws_of(g)
            lane_ok = slot_ok[:, None] & filled & (rs >= lo)
        return rk.reshape(-1), lane_ok.reshape(-1)

    run_keys, run_valid = jax.vmap(row)(jnp.arange(c))
    return ReplayRuns(ugroups, run_keys, run_valid, num)


def _key_sentinel(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).max
    return jnp.inf


def merged_window(spec: PaneStoreSpec, run_keys: Array, run_valid: Array
                  ) -> tuple[Array, Array]:
    """Merge one row's presorted runs and compact the live lanes to the
    front: returns ``(keys_sorted_live_prefix, cnt)``.  This is the
    reference rendering of the Pallas kernel's merge + shared butterfly
    compaction."""
    mk, mv = sorter.merge_presorted(
        (run_keys, run_valid.astype(jnp.int32)), run=spec.wa, num_keys=1)
    mv = mv == 1
    # stable compaction of live lanes (keeps key order): scatter by rank
    n = mk.shape[-1]
    rank = jnp.cumsum(mv.astype(jnp.int32)) - mv.astype(jnp.int32)
    idx = jnp.where(mv, rank, n)
    out = jnp.full((n + 1,), _key_sentinel(mk.dtype), mk.dtype).at[idx].set(
        mk, mode="drop")[:n]
    return out, jnp.sum(mv.astype(jnp.int32))


def _direct_tails(keys_c: Array, cnt: Array, names, *, key_dtype,
                  interpolate: bool) -> dict:
    """Every DIRECT_OPS value from one compacted, key-sorted live prefix —
    shared by the reference replay and mirrored in the Pallas kernel."""
    n = keys_c.shape[-1]
    lane = jnp.arange(n)
    live = lane < cnt
    nonempty = cnt > 0
    out = {}
    for name in names:
        if name == "count":
            out[name] = cnt
        elif name == "sum":
            acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
            out[name] = jnp.sum(jnp.where(live, keys_c, 0).astype(acc))
        elif name == "min":
            out[name] = jnp.where(nonempty, keys_c[0],
                                  jnp.zeros((), keys_c.dtype))
        elif name == "max":
            v = jnp.sum(jnp.where(lane == cnt - 1, keys_c, 0))
            out[name] = jnp.where(nonempty, v, 0).astype(keys_c.dtype)
        elif name == "mean":
            # sum in the combiner's accumulator dtype (exact for int keys),
            # divide once — the same formula the per-pane partial fast path
            # uses, so both paths produce bit-identical means
            acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
            s = jnp.sum(jnp.where(live, keys_c, 0).astype(acc))
            out[name] = (s.astype(jnp.float32)
                         / jnp.maximum(cnt, 1).astype(jnp.float32))
        elif name == "median":
            lo = jnp.sum(jnp.where(lane == jnp.maximum(cnt - 1, 0) // 2,
                                   keys_c, 0))
            hi = jnp.sum(jnp.where(lane == cnt // 2, keys_c, 0))
            if interpolate:
                med = (lo.astype(jnp.float32) + hi.astype(jnp.float32)) / 2.0
            else:
                med = lo.astype(keys_c.dtype)
            out[name] = jnp.where(nonempty, med, 0).astype(med.dtype)
        elif name == "distinct_count":
            prev = jnp.concatenate(
                [jnp.full((1,), _key_sentinel(keys_c.dtype), keys_c.dtype),
                 keys_c[:-1]])
            neq = (keys_c != prev) & live
            out[name] = jnp.sum(neq.astype(jnp.int32))
        else:  # pragma: no cover - guarded by replay()
            raise ValueError(f"{name} is not a direct replay op")
    return out


def replay_rows(spec: PaneStoreSpec, run_keys: Array, run_valid: Array,
                ops, names, *, key_dtype, interpolate: bool):
    """Merge + tails over ``[R, S*WA]`` gathered replay rows — the batched
    form of :func:`replay`'s merge path (``R = NE * C`` when the per-group
    batch entry evaluates every chunk's rows in one pass).  Returns
    ``({name: values [R]}, cnt [R])``."""
    fallback = [(op, nm) for op, nm in zip(ops, names)
                if nm not in DIRECT_OPS]
    direct = [nm for nm in names if nm in DIRECT_OPS]

    def row(rk, rv):
        kc, cnt = merged_window(spec, rk, rv)
        vals = _direct_tails(kc, cnt, direct, key_dtype=key_dtype,
                             interpolate=interpolate)
        if fallback:
            gc = jnp.where(jnp.arange(kc.shape[-1]) < cnt, 0, PAD_GROUP)
            for op, nm in fallback:
                r = _engine._group_by_aggregate(gc, kc, op)
                vals[nm] = r.values[0]
        return vals, cnt

    return jax.vmap(row)(run_keys, run_valid)


def _replay_partials(spec: PaneStoreSpec, state: PaneStoreState, names):
    """The per-pane partial fast path (count mode): every
    :data:`PANE_PARTIAL_OPS` value from per-slot masked partial aggregates
    — O(C·WA + C²) elementwise work, no S·WA-wide merge network and no
    per-row pane gather.  The partials are set-based, so neither the pane
    sort order nor the merge matters; the merge-replay path stays reserved
    for median/distinct_count (and float sum/mean — see
    :func:`partial_path_names`).

    Bit-exact vs the merge path for every op it serves: integer sums
    accumulate in the combiner's accumulator dtype, min/max/count are
    order-invariant, and mean derives from the exact sum the same way
    :func:`_direct_tails` does.

    Returns ``(ugroups [C], {name: values [C]}, valid [C], num)`` in the
    same row layout as the merge path (:func:`_slot_directory` rows).
    """
    wa = spec.wa
    c = state.owner.shape[0]
    occ = state.owner != PAD_GROUP
    imin = jnp.iinfo(jnp.int32).min
    # per-slot m_g of the slot's owner: within a group, pane bases are
    # contiguous (retirement and eviction both free the oldest pane first),
    # so the owner's newest pane maximises base + count over its slots
    span = jnp.where(occ, state.base + state.count, imin)
    same = occ[:, None] & (state.owner[:, None] == state.owner[None, :])
    m = jnp.max(jnp.where(same, span[None, :], imin), axis=1)
    lo = m - spec.ws_of(state.owner)

    lanes = jnp.arange(wa)[None, :]
    live = (occ[:, None] & (lanes < state.count[:, None])
            & (state.seqs >= lo[:, None]))

    _perm, ugroups, _off, _ns, num, _n_occ = _slot_directory(state)
    rows = ((ugroups[:, None] == state.owner[None, :]) & occ[None, :]
            & (ugroups[:, None] != PAD_GROUP))

    key_dtype = state.keys.dtype
    hi = _key_sentinel(key_dtype)
    lo_sent = (jnp.iinfo(key_dtype).min
               if jnp.issubdtype(key_dtype, jnp.integer) else -jnp.inf)

    pc = jnp.sum(live.astype(jnp.int32), axis=1)             # [C] per slot
    cnt = jnp.sum(jnp.where(rows, pc[None, :], 0), axis=1)   # [C] per row
    rsum = None
    if any(nm in ("sum", "mean") for nm in names):
        acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
        psum = jnp.sum(jnp.where(live, state.keys, 0).astype(acc), axis=1)
        rsum = jnp.sum(jnp.where(rows, psum[None, :],
                                 jnp.zeros((), acc)), axis=1)
    out = {}
    for name in names:
        if name == "count":
            out[name] = cnt
        elif name == "sum":
            out[name] = rsum
        elif name == "mean":
            out[name] = (rsum.astype(jnp.float32)
                         / jnp.maximum(cnt, 1).astype(jnp.float32))
        elif name == "min":
            pmin = jnp.min(jnp.where(live, state.keys, hi), axis=1)
            v = jnp.min(jnp.where(rows, pmin[None, :], hi), axis=1)
            out[name] = jnp.where(cnt > 0, v,
                                  jnp.zeros((), key_dtype)).astype(key_dtype)
        elif name == "max":
            pmax = jnp.max(jnp.where(live, state.keys, lo_sent), axis=1)
            v = jnp.max(jnp.where(rows, pmax[None, :], lo_sent), axis=1)
            out[name] = jnp.where(cnt > 0, v,
                                  jnp.zeros((), key_dtype)).astype(key_dtype)
        else:  # pragma: no cover - guarded by partial_path_names
            raise ValueError(f"{name} is not a partial-path op")
    valid = jnp.arange(c) < num
    return ugroups, out, valid, num


def time_window_ends(spec: PaneStoreSpec, state: PaneStoreState,
                     next_end: Array, wm: Array, rows: int):
    """Closed hopping windows of a time-mode store: the ends of the
    windows, oldest first, that hold a stored tuple, end at or after the
    cursor ``next_end`` and at or before the watermark ``wm``; at most
    ``rows`` of them.  Returns ``(ends [rows], emit [rows], cursor)``
    (``ends`` is TS_FLOOR where ``emit`` is False)."""
    s, rng = spec.slide, spec.time_range
    lanes = jnp.arange(spec.wa)[None, :]
    occ = (state.owner != PAD_GROUP)[:, None] & (lanes < state.count[:, None])
    ts = state.seqs
    c = jnp.asarray(next_end, jnp.int32)
    ends, emit = [], []
    go = jnp.asarray(True)
    big = jnp.iinfo(jnp.int32).max
    for _ in range(rows):
        # each tuple's first window at or after the cursor, where one holds it
        e_t = jnp.maximum(c, (jnp.floor_divide(ts, s) + 1) * s)
        ok = occ & (ts >= c - rng) & (e_t - rng <= ts)
        e = jnp.min(jnp.where(ok, e_t, big))
        go = go & (e != big) & (e <= wm)
        ends.append(jnp.where(go, e, TS_FLOOR))
        emit.append(go)
        c = jnp.where(go, e + s, c)
    return jnp.stack(ends), jnp.stack(emit), c


def emit_time_windows(spec: PaneStoreSpec, state: PaneStoreState, ops,
                      next_end: Array, wm: Array, rows: int, *,
                      interpolate: bool = False):
    """Evaluate each closed window of :func:`time_window_ends` with
    :func:`replay` at its end.  Returns ``((groups [rows, C], values,
    valid, num [rows], ends [rows]), cursor)``; rows past the emitted
    windows hold only pad lanes."""
    ends, go, cursor = time_window_ends(spec, state, next_end, wm, rows)

    def empty():
        g, values, valid, num = jax.eval_shape(
            lambda: replay(spec, state, ops, interpolate=interpolate,
                           eval_time=jnp.zeros((), jnp.int32)))
        return (jnp.full(g.shape, PAD_GROUP, jnp.int32),
                {k: jnp.zeros(v.shape, v.dtype) for k, v in values.items()},
                jnp.zeros(valid.shape, bool), jnp.zeros((), jnp.int32))

    def one(args):
        e, g = args
        return jax.lax.cond(
            g, lambda: replay(spec, state, ops, interpolate=interpolate,
                              eval_time=e), empty)

    groups, values, valid, num = jax.lax.map(one, (ends, go))
    return (groups, values, valid, num, ends), cursor


def replay(spec: PaneStoreSpec, state: PaneStoreState, ops, *,
           interpolate: bool = False, eval_time: Array | None = None):
    """Evaluate every live group's window from the store (reference path).

    Returns ``(groups [C], {name: values [C]}, valid [C], num_groups)`` —
    the per-evaluation analogue of one :class:`repro.query.AggResult` row.
    Ops are routed by *name*: :data:`PANE_PARTIAL_OPS` take the per-pane
    partial fast path (:func:`_replay_partials` — no gather, no merge);
    the remaining DIRECT_OPS are computed straight off the merged window
    (element-exact vs the naive keep-last-``WS_g`` reference; a
    :class:`Combiner` instance carrying one of those names is assumed to
    mean the standard op); any other combiner falls back to an engine pass
    over the merged, compacted window — exact vs a full re-sort of the
    same window.

    Time mode evaluates the shared window ``[eval_time - time_range,
    eval_time)`` (normally ``eval_time`` = the watermark) and always
    merge-replays (the shared time window has no per-group seq bounds).
    """
    names = [op.name if isinstance(op, Combiner) else op for op in ops]
    key_dtype = state.keys.dtype
    c = spec.capacity

    psel = ([False] * len(names) if spec.is_time
            else partial_path_names(names, key_dtype))
    partial_names = [nm for nm, sel in zip(names, psel) if sel]
    merge_pairs = [(op, nm) for (op, nm), sel in zip(zip(ops, names), psel)
                   if not sel]

    values = {}
    if partial_names:
        ugroups, pvals, pvalid, pnum = _replay_partials(spec, state,
                                                        partial_names)
        values.update(pvals)
        if not merge_pairs:
            values = {name: jnp.where(pvalid, v, jnp.zeros((), v.dtype))
                      for name, v in values.items()}
            return ugroups, values, pvalid, pnum

    runs = gather_runs(spec, state, eval_time=eval_time)
    mvals, cnts = replay_rows(
        spec, runs.run_keys, runs.run_valid,
        [op for op, _ in merge_pairs], [nm for _, nm in merge_pairs],
        key_dtype=key_dtype, interpolate=interpolate)
    values.update(mvals)
    valid = jnp.arange(c) < runs.num_groups
    if spec.is_time:
        # a group may still own slots while every one of its tuples sits
        # outside [eval_time - R, eval_time): drop those rows (stable
        # scatter-compaction, same trick as merged_window)
        keep = valid & (cnts > 0)
        rank = jnp.cumsum(keep.astype(jnp.int32)) - keep.astype(jnp.int32)
        idx = jnp.where(keep, rank, c)
        groups_o = jnp.full((c + 1,), PAD_GROUP, jnp.int32).at[idx].set(
            runs.groups, mode="drop")[:c]
        num = jnp.sum(keep.astype(jnp.int32))
        valid = jnp.arange(c) < num
        values = {name: jnp.zeros((c + 1,), v.dtype).at[idx].set(
            v, mode="drop")[:c] for name, v in values.items()}
        values = {name: jnp.where(valid, v, jnp.zeros((), v.dtype))
                  for name, v in values.items()}
        return groups_o, values, valid, num
    values = {name: jnp.where(valid, v, jnp.zeros((), v.dtype))
              for name, v in values.items()}
    return runs.groups, values, valid, runs.num_groups
