"""Event-time subsystem: watermarks, bounded-lateness reorder, time windows.

Every window elsewhere in the engine counts tuples; the paper's target
workloads (bank security, medical sensors) carry *timestamps*, arrive out
of order, and skew.  This module adds the event-time layer underneath
``Window(range=..., slide=...)`` (``repro.query``):

  * :class:`WatermarkTracker` — the per-shard low-watermark.  With bounded
    out-of-orderness (every tuple arrives within ``max_lateness`` time
    units of the stream's maximum seen timestamp) the watermark
    ``wm = max_ts - max_lateness`` is a promise: no future tuple has
    ``ts < wm``, so any window ending at or before ``wm`` may close.
    Sharded streams take ``wm = min`` over the shards' watermarks
    (:func:`merge_watermarks`) — a tuple may still arrive on the
    slowest shard.
  * a fixed-capacity **bounded-lateness reorder buffer**
    (:class:`ReorderSpec` / :func:`reorder_push`) — the software rendering
    of Gulisano et al.'s multiway out-of-order ingest stage: one tuple in,
    at most one tuple out per cycle (a ``lax.scan`` of constant-shape
    vector work, like the pane store's ingest), releasing the buffered
    minimum-timestamp tuple once the watermark passes it and flagging
    tuples later than ``max_lateness`` as **dropped** (never silently
    aggregated).  Emitted timestamps are nondecreasing by construction,
    so downstream time panes see an in-order stream.
  * **time-window framing** (:func:`time_window_layout` /
    :func:`frame_time_windows`) — batch queries sort by timestamp once and
    frame each window ``[e - range, e)`` (one evaluation per ``slide``
    units) as a static-width row; window boundaries are data positions,
    computed host-side from the *concrete* timestamps (the static-shape
    contract: window count and width are shapes).

The replay-free two-stack aggregation over these frames lives in
:mod:`repro.core.twostack`; the watermark-evicted time panes of the
streaming path live in :mod:`repro.core.panestore` (time mode).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sorter
from repro.obs.trace import stage

Array = jax.Array

#: initial "no tuple seen" timestamp — low enough that wm = TS_MIN - L never
#: releases anything, high enough that int32 arithmetic cannot wrap
TS_MIN = -(2 ** 30)

#: hard ceiling on the number of time windows one batch may frame (a sparse
#: stream with a tiny slide would otherwise explode the static window axis)
MAX_TIME_WINDOWS = 65536

_I32_MAX = jnp.iinfo(jnp.int32).max


# --------------------------------------------------------------------------
# watermarks
# --------------------------------------------------------------------------

class WatermarkTracker(NamedTuple):
    """Low-watermark state of one (timestamp, group, value) stream shard:
    the maximum timestamp observed so far (int32 scalar)."""
    max_ts: Array


def init_tracker() -> WatermarkTracker:
    return WatermarkTracker(max_ts=jnp.asarray(TS_MIN, jnp.int32))


def observe(tracker: WatermarkTracker, ts: Array,
            live: Array | None = None) -> WatermarkTracker:
    """Fold a batch of timestamps into the tracker (``live`` masks lanes)."""
    ts = jnp.asarray(ts, jnp.int32)
    if live is not None:
        ts = jnp.where(live, ts, TS_MIN)
    return WatermarkTracker(jnp.maximum(tracker.max_ts, jnp.max(ts)))


def watermark(tracker: WatermarkTracker, max_lateness: int) -> Array:
    """``wm = max_ts - max_lateness``: no future in-contract tuple is
    earlier than this."""
    return tracker.max_ts - jnp.asarray(max_lateness, jnp.int32)


def merge_watermarks(wms) -> Array:
    """The cross-shard merge rule: the stream's watermark is the *minimum*
    over its shards' watermarks (a tuple may still arrive on the slowest
    shard).  ``wms`` is a sequence of scalars or a stacked array."""
    wms = jnp.asarray(wms) if not isinstance(wms, jax.Array) else wms
    return jnp.min(wms)


# --------------------------------------------------------------------------
# bounded-lateness reorder buffer
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReorderSpec:
    """Static configuration of one reorder buffer (hashable; jit-static).

    ``capacity``: buffered tuple slots (power of two).  ``max_lateness``:
    the bounded-out-of-orderness contract — a tuple arriving more than this
    many time units behind the maximum seen timestamp is *dropped* (and
    flagged), never aggregated out of order.
    """
    capacity: int
    max_lateness: int

    def __post_init__(self):
        if self.capacity <= 0 or self.capacity & (self.capacity - 1):
            raise ValueError(f"reorder capacity must be a positive power of "
                             f"two, got {self.capacity}")
        if self.max_lateness < 0:
            raise ValueError(f"max_lateness must be >= 0, "
                             f"got {self.max_lateness}")


class ReorderState(NamedTuple):
    """The reorder buffer (one pytree — part of the streaming carry).

    ``seq`` is the arrival sequence number (the tie-break that keeps equal
    timestamps in arrival order); ``max_ts`` is the embedded
    :class:`WatermarkTracker`; ``last_emit`` enforces nondecreasing
    emission timestamps even across forced (capacity) releases;
    ``dropped`` counts late-dropped tuples over the stream's lifetime.
    """
    ts: Array         # [C] int32
    grp: Array        # [C] int32
    val: Array        # [C] key dtype
    seq: Array        # [C] int32
    occ: Array        # [C] bool
    max_ts: Array     # [] int32 (watermark tracker)
    last_emit: Array  # [] int32
    seq_clock: Array  # [] int32
    dropped: Array    # [] int32


class ReorderEmit(NamedTuple):
    """Per-input-lane emissions of one :func:`reorder_push` (at most one
    tuple out per tuple in).  ``late`` flags *input* lanes dropped as too
    late; ``live`` flags output lanes carrying a released tuple."""
    ts: Array      # [N] int32
    groups: Array  # [N] int32
    keys: Array    # [N]
    live: Array    # [N] bool
    late: Array    # [N] bool


def init_reorder(spec: ReorderSpec, key_dtype=jnp.int32) -> ReorderState:
    c = spec.capacity
    return ReorderState(
        ts=jnp.zeros((c,), jnp.int32),
        grp=jnp.zeros((c,), jnp.int32),
        val=jnp.zeros((c,), key_dtype),
        seq=jnp.zeros((c,), jnp.int32),
        occ=jnp.zeros((c,), bool),
        max_ts=jnp.asarray(TS_MIN, jnp.int32),
        last_emit=jnp.asarray(TS_MIN, jnp.int32),
        seq_clock=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


def _reorder_cycle(spec: ReorderSpec, st: ReorderState, t, g, k, lv,
                   release_wm, late_wm=None, counters=None):
    """One in / at most one out.  The incoming tuple (dead when ``lv`` is
    False) first advances the watermark; a buffered (or the incoming)
    minimum-timestamp tuple is released when the watermark passes it —
    or unconditionally when the buffer would overflow (the forced release
    keeps later, in-contract tuples from being starved; ``last_emit``
    then drops stragglers that would break emission order).

    ``late_wm`` overrides the lateness threshold (default: the running
    local watermark).  The sharded path passes the *previously merged*
    global watermark: a shard fed the tail slice of every batch sees an
    inflated local maximum, and a tuple is only unrecoverable once an
    already-emitted evaluation (gated on the merged watermark) has passed
    it."""
    c = spec.capacity
    lanes = jnp.arange(c)

    max_ts = jnp.maximum(st.max_ts, jnp.where(lv, t, TS_MIN))
    wm = max_ts - spec.max_lateness
    release = wm if release_wm is None else release_wm

    late_floor = wm if late_wm is None else late_wm
    late = lv & ((t < late_floor) | (t < st.last_emit))
    insert = lv & ~late

    # the buffered minimum by (ts, seq) — two-step argmin keeps everything
    # in int32 (no packed 64-bit comparator needed)
    ts_all = jnp.where(st.occ, st.ts, _I32_MAX)
    mts = jnp.min(ts_all)
    any_occ = jnp.any(st.occ)
    lane = jnp.argmin(jnp.where(st.occ & (st.ts == mts), st.seq, _I32_MAX))
    full = jnp.sum(st.occ.astype(jnp.int32)) == c

    # the incoming tuple wins ties never (its seq is the largest), so it is
    # the candidate minimum only when strictly earlier than the buffer's
    inc_min = insert & ((t < mts) | ~any_occ)
    pop_inc = inc_min & ((t <= release) | full)
    pop_buf = ~pop_inc & any_occ & ((mts <= release) | (full & insert))

    et = jnp.where(pop_inc, t, st.ts[lane])
    eg = jnp.where(pop_inc, g.astype(jnp.int32), st.grp[lane])
    ek = jnp.where(pop_inc, k, st.val[lane])
    ev = pop_inc | pop_buf

    occ = st.occ & ~(pop_buf & (lanes == lane))
    do_ins = insert & ~pop_inc
    slot = jnp.argmax(~occ)          # a free lane exists whenever do_ins
    at = do_ins & (lanes == slot)
    new = ReorderState(
        ts=jnp.where(at, t, st.ts),
        grp=jnp.where(at, g.astype(jnp.int32), st.grp),
        val=jnp.where(at, k, st.val),
        seq=jnp.where(at, st.seq_clock, st.seq),
        occ=occ | at,
        max_ts=max_ts,
        last_emit=jnp.where(ev, jnp.maximum(st.last_emit, et), st.last_emit),
        seq_clock=st.seq_clock + do_ins.astype(jnp.int32),
        dropped=st.dropped + late.astype(jnp.int32),
    )
    if counters is None:
        return new, (et, eg, ek, ev, late)
    from repro.obs import counters as _c
    forced = (pop_inc & (t > release)) | (pop_buf & (mts > release))
    counters = _c.bump(counters, "reorder_forced_pops",
                       forced.astype(jnp.int32))
    counters = _c.high_water(counters, "reorder_depth_hwm",
                             jnp.sum(new.occ.astype(jnp.int32)))
    return new, (et, eg, ek, ev, late), counters


def _reorder_drain(spec: ReorderSpec, state: ReorderState, release: Array
                   ) -> tuple[ReorderEmit, ReorderState]:
    """Release *every* buffered tuple the gate has passed (``ts <=
    release``), sorted by (ts, seq), as one ``[capacity]`` emission batch.
    The per-cycle pop of :func:`_reorder_cycle` releases at most one tuple
    per arrival, so a watermark jump leaves order-dependent backlog; this
    end-of-push drain restores the invariant that the released set is
    exactly ``{t : t <= release}`` — the arrival-order independence
    (bit-identity) guarantee."""
    c = spec.capacity
    rel = state.occ & (state.ts <= release)
    ts_m = jnp.where(rel, state.ts, _I32_MAX)
    seq_m = jnp.where(rel, state.seq, _I32_MAX)
    sts, _, sg, sk = jax.lax.sort(
        (ts_m, seq_m, state.grp, state.val), num_keys=2)
    num = jnp.sum(rel.astype(jnp.int32))
    live = jnp.arange(c) < num
    last = jnp.where(num > 0, sts[jnp.maximum(num - 1, 0)], state.last_emit)
    state = state._replace(
        occ=state.occ & ~rel,
        last_emit=jnp.maximum(state.last_emit, last))
    emit = ReorderEmit(jnp.where(live, sts, 0), sg, sk, live,
                       jnp.zeros((c,), bool))
    return emit, state


def reorder_push(spec: ReorderSpec, state: ReorderState, ts: Array,
                 groups: Array, keys: Array, *,
                 n_valid: Array | None = None,
                 release_wm: Array | None = None,
                 late_wm: Array | None = None,
                 drain_wm: Array | None = None,
                 counters=None):
    """Stream one batch through the reorder buffer: a ``lax.scan`` of the
    one-in/one-out cycle, then a drain of everything else the final
    watermark has passed (so after every push the released set is exactly
    the tuples at or below the release gate, independent of arrival
    order).  Emissions carry ``capacity`` extra drain lanes after the
    ``N`` per-cycle lanes; ts-nondecreasing across the whole batch.

    ``release_wm`` overrides the per-cycle release gate with an externally
    merged watermark (the sharded path: tuples release only once *every*
    shard's watermark has passed them).  The per-cycle gate MUST be causal
    (not ahead of any tuple still arriving in this batch) — an eager
    release advances ``last_emit`` and would kill later in-contract
    arrivals; a gate that looks ahead belongs in ``drain_wm``, applied
    once after the whole batch is buffered (defaults to ``release_wm``,
    then to the post-push local watermark).  ``late_wm`` overrides the
    late-drop threshold (the sharded path passes the previous push's
    merged watermark — see :func:`_reorder_cycle`).

    With ``counters`` (an :mod:`repro.obs.counters` dict) returns
    ``(emit, state, counters)``, recording the buffer-depth high-water
    mark and capacity-forced pops across every cycle of the push."""
    ts = jnp.asarray(ts, jnp.int32)
    groups = jnp.asarray(groups, jnp.int32)
    keys = jnp.asarray(keys, state.val.dtype)
    n = ts.shape[-1]
    live = (jnp.ones((n,), bool) if n_valid is None
            else jnp.arange(n) < n_valid)

    if counters is None:
        def step(st, x):
            t, g, k, lv = x
            return _reorder_cycle(spec, st, t, g, k, lv, release_wm, late_wm)

        with stage("reorder"):
            state, (ets, egs, eks, evs, lates) = jax.lax.scan(
                step, state, (ts, groups, keys, live))
    else:
        from repro.obs import counters as _c
        counters = _c.ensure(counters, ("reorder_depth_hwm",
                                        "reorder_forced_pops"))

        def step(carry, x):
            st, cnt = carry
            t, g, k, lv = x
            st, out, cnt = _reorder_cycle(spec, st, t, g, k, lv, release_wm,
                                          late_wm, counters=cnt)
            return (st, cnt), out

        with stage("reorder"):
            (state, counters), (ets, egs, eks, evs, lates) = jax.lax.scan(
                step, (state, counters), (ts, groups, keys, live))
    gate = drain_wm if drain_wm is not None else release_wm
    release = state.max_ts - spec.max_lateness if gate is None else gate
    with stage("reorder"):
        drain, state = _reorder_drain(spec, state, release)
    emit = ReorderEmit(
        jnp.concatenate([ets, drain.ts]),
        jnp.concatenate([egs, drain.groups]),
        jnp.concatenate([eks, drain.keys]),
        jnp.concatenate([evs, drain.live]),
        jnp.concatenate([lates, drain.late]))
    if counters is None:
        return emit, state
    return emit, state, counters


def reorder_flush(spec: ReorderSpec, state: ReorderState
                  ) -> tuple[ReorderEmit, ReorderState]:
    """Drain the buffer: every held tuple, sorted by (ts, seq), as one
    ``[capacity]`` emission batch.  The returned state is empty (watermark,
    drop counter and emission floor are kept)."""
    c = spec.capacity
    ts_m = jnp.where(state.occ, state.ts, _I32_MAX)
    seq_m = jnp.where(state.occ, state.seq, _I32_MAX)
    sts, _, sg, sk = jax.lax.sort(
        (ts_m, seq_m, state.grp, state.val), num_keys=2)
    num = jnp.sum(state.occ.astype(jnp.int32))
    live = jnp.arange(c) < num
    last = jnp.where(num > 0, sts[jnp.maximum(num - 1, 0)], state.last_emit)
    drained = state._replace(
        occ=jnp.zeros((c,), bool),
        last_emit=jnp.maximum(state.last_emit, last))
    emit = ReorderEmit(jnp.where(live, sts, 0), sg, sk, live,
                       jnp.zeros((c,), bool))
    return emit, drained


# --------------------------------------------------------------------------
# batch time-window framing
# --------------------------------------------------------------------------

def concrete_timestamps(timestamps) -> np.ndarray:
    """Timestamps as a host array — window count and width are *shapes*,
    so they must be computed from concrete values (not tracers)."""
    try:
        ts = np.asarray(timestamps)
    except jax.errors.TracerArrayConversionError:
        raise ValueError(
            "time-range windows compute the window count and width from "
            "concrete timestamps (they are static shapes); call execute() "
            "outside jit, or use the streaming path (Query(streaming=True))"
        ) from None
    if ts.ndim != 1:
        raise ValueError(f"timestamps must be a rank-1 column, "
                         f"got shape {ts.shape}")
    return ts.astype(np.int64)


class TimeLayout(NamedTuple):
    """Host-side layout of one batch's time windows over the ts-sorted
    stream: window ``j`` covers tuple positions ``[starts[j], ends[j])``
    and the time range ``[end_times[j] - range, end_times[j])``."""
    order: np.ndarray      # [N] ts-ascending stable sort permutation
    starts: np.ndarray     # [NW] first tuple index of each window
    ends: np.ndarray       # [NW] one past the last tuple index
    end_times: np.ndarray  # [NW] window end timestamps (multiples of slide)
    wcap: int              # power-of-two max tuples per window (>= 1)


def time_window_layout(ts: np.ndarray, time_range: int,
                       slide: int) -> TimeLayout:
    """Window boundaries over the ts-sorted stream: one window per ``slide``
    units, ending at multiples of ``slide``, from the first multiple after
    the earliest tuple through the first multiple after the latest."""
    order = np.argsort(ts, kind="stable")
    tss = ts[order]
    n = tss.shape[0]
    if n == 0:
        return TimeLayout(order, np.zeros(0, np.int64), np.zeros(0, np.int64),
                          np.zeros(0, np.int64), 1)
    nw = int(tss[-1] // slide - tss[0] // slide) + 1
    if nw > MAX_TIME_WINDOWS:
        raise ValueError(
            f"slide={slide} frames {nw} windows over this batch's "
            f"timestamp span (> {MAX_TIME_WINDOWS}); use a larger slide "
            f"or the streaming path")
    end_times = (np.arange(nw, dtype=np.int64)
                 + tss[0] // slide + 1) * slide
    starts = np.searchsorted(tss, end_times - time_range, side="left")
    ends = np.searchsorted(tss, end_times, side="left")
    wcap = sorter.next_pow2(max(1, int((ends - starts).max())))
    return TimeLayout(order, starts, ends, end_times, wcap)


def frame_time_windows(layout: TimeLayout, groups_sorted: Array,
                       keys_sorted: Array, pad_group: int
                       ) -> tuple[Array, Array, Array]:
    """Gather the ts-sorted stream into static ``[NW, wcap]`` window rows
    (dead lanes carry ``pad_group`` / zero keys).  Returns
    ``(frame_groups, frame_keys, counts)``."""
    n = groups_sorted.shape[-1]
    starts = jnp.asarray(layout.starts, jnp.int32)
    cnt = jnp.asarray(layout.ends - layout.starts, jnp.int32)
    idx = starts[:, None] + jnp.arange(layout.wcap, dtype=jnp.int32)[None, :]
    live = jnp.arange(layout.wcap)[None, :] < cnt[:, None]
    idx = jnp.clip(idx, 0, max(n - 1, 0))
    fg = jnp.where(live, groups_sorted[idx], pad_group)
    fk = jnp.where(live, keys_sorted[idx],
                   jnp.zeros((), keys_sorted.dtype))
    return fg, fk, cnt
