"""Sliding-window aggregation (SWAG) — the paper's Fig. 4 pipeline.

    window buffer (WS, WA)  ->  small sorter  ->  group-by-aggregate engine

Queries are of the form "aggregate the last WS tuples per group id, advancing
by WA" (time = tuple count, as in the paper's primary case).  Sorting each
window by group reduces SWAG to the engine's sorted-stream contract; because
the sorter sees the whole window before flushing, *non-incremental* functions
(median) get the group cardinalities for free — the paper's key argument for
the sort-based SWAG design (vs. hash sets sized for the worst case).

Pane architecture
-----------------
When ``WA < WS`` consecutive windows share ``WS - WA`` tuples, so re-sorting
every window wastes the work the paper's double-buffered small sorters
amortise.  The pane path (:func:`swag_panes`) partitions the stream into
``WA``-sized **panes**, sorts each pane **once**, and assembles each window
from its ``P = WS/WA`` presorted panes:

  * **merge path** (median, mean, and any op without a single-array
    incremental state): the P panes are merged with a bitonic *merge* network
    (:func:`repro.core.sorter.merge_presorted`, ~log P * log WS sweeps
    instead of the full log^2 WS re-sort).  A fully (group, key)-sorted
    sequence of a multiset is unique, so the merged window is *identical* to
    the re-sorted window and the downstream engine output is bit-exact.
  * **shared-partial path** (sum / count / min / max): each pane is reduced
    to per-group partial aggregates by **one** engine pass, and every window
    combines its P panes' compacted partials (a group-only merge of P short
    presorted runs + one engine pass with an identity-lift combiner).  The
    per-tuple work is paid once per pane instead of once per window.

Dispatch rules (``panes=None`` — spelled ``Window(panes=None)`` in the
query API, which is the preferred entry; :func:`swag` / :func:`swag_median`
remain as deprecated shims): the pane path is taken automatically when
``WS % WA == 0``, both are powers of two (the merge network's wiring
constraint), and ``WA < WS``; otherwise the original re-sort path runs.
``panes=True``/``False`` forces either.  :func:`swag_multi` is the fused
multi-op variant the query planner uses: one pane sort (or one per-window
re-sort) shared by every requested combiner tail.

Windows are framed with a strided gather (the "simple buffering arrangement"
that reuses tuples when WA < WS) and processed with ``vmap`` — the software
analogue of the paper's double-buffered sorters.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core import panestore as _panestore
from repro.core import segscan, sorter
from repro.core.combiners import (Combiner, get_combiner,
                                  partial_combiner as _mk_partial_combiner)
from repro.obs.trace import stage

Array = jax.Array

#: ops whose engine state is a single array combined by an associative,
#: commutative op with identity finalize — eligible for shared partials
PARTIAL_OPS = frozenset({"sum", "count", "min", "max"})


def num_windows(n: int, ws: int, wa: int) -> int:
    if ws > n:
        return 0
    return (n - ws) // wa + 1


def frame_windows(x: Array, ws: int, wa: int) -> Array:
    """[N] -> [num_windows, WS] strided view (tuples reused when WA < WS)."""
    nw = num_windows(x.shape[-1], ws, wa)
    idx = jnp.arange(nw)[:, None] * wa + jnp.arange(ws)[None, :]
    return x[..., idx]


def pane_compatible(ws: int, wa: int) -> bool:
    """True when the pane fast path applies: WS a multiple of WA, both powers
    of two (the bitonic merge network's wiring constraint), WA < WS."""
    return (0 < wa < ws and ws % wa == 0
            and ws & (ws - 1) == 0 and wa & (wa - 1) == 0)


def frame_panes(x: Array, wa: int, num_panes: int) -> Array:
    """[N] -> [num_panes, WA] non-overlapping panes (trailing remainder that
    can never complete a window is dropped)."""
    return x[..., :num_panes * wa].reshape(x.shape[:-1] + (num_panes, wa))


def resolve_panes(ws: int, wa: int, n: int, panes: bool | None, *,
                  presorted: bool = False) -> bool:
    """Resolve the shared ``panes`` tri-state used by every SWAG entry point.

    ``None`` auto-dispatches (pane-compatible shapes, >= 1 window, input not
    presorted); ``False`` forces the re-sort path; ``True`` forces panes and
    *raises* when they cannot apply — never a silent fallback.
    """
    if panes is None:
        return ((not presorted) and pane_compatible(ws, wa)
                and num_windows(n, ws, wa) > 0)
    if not panes:
        return False
    if presorted:
        raise ValueError("panes=True cannot apply to presorted windows — "
                         "the pane path frames and sorts the raw stream")
    if not (pane_compatible(ws, wa) or (ws == wa and ws & (ws - 1) == 0)):
        raise ValueError(f"pane path needs power-of-two WS/WA with WA "
                         f"dividing WS, got ws={ws} wa={wa}")
    if num_windows(n, ws, wa) == 0:
        raise ValueError(f"no complete window: n={n} < ws={ws}")
    return True


def _pane_windows(panes: Array, nw: int, p: int) -> Array:
    """[NP, WA, ...] -> [NW, P*WA, ...]: window w = panes w .. w+P-1."""
    widx = jnp.arange(nw)[:, None] + jnp.arange(p)[None, :]
    stacked = panes[widx]  # [NW, P, WA, ...]
    return stacked.reshape((nw, p * panes.shape[1]) + panes.shape[2:])


def _swag(groups: Array, keys: Array, *, ws: int, wa: int, op="sum",
          presorted: bool = False, use_xla_sort: bool = False,
          panes: bool | None = None) -> _engine.GroupAggResult:
    """Internal (non-deprecated) sliding-window group-by-aggregate.

    Returns a :class:`GroupAggResult` whose arrays carry a leading
    ``[num_windows]`` axis.  ``panes=None`` auto-dispatches to the
    sort-once-per-pane fast path when :func:`pane_compatible` (see module
    docstring); the result is element-exact either way.
    """
    if op == "median":
        # keep the contract shape-independent: median returns a different
        # result type and has its own entry point
        raise ValueError("op='median' is not a combiner — use swag_median "
                         "(or swag_panes, which returns a MedianResult)")
    if resolve_panes(ws, wa, groups.shape[-1], panes, presorted=presorted):
        return swag_panes(groups, keys, ws=ws, wa=wa, op=op,
                          use_xla_sort=use_xla_sort)

    gw = frame_windows(groups, ws, wa)
    kw = frame_windows(keys, ws, wa)

    def per_window(g, k):
        if not presorted:
            srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs
            g, k = srt(g, k, full_width=True)
        return _engine._group_by_aggregate(g, k, op)

    return jax.vmap(per_window)(gw, kw)


def swag(groups: Array, keys: Array, *, ws: int, wa: int, op="sum",
         presorted: bool = False, use_xla_sort: bool = False,
         panes: bool | None = None) -> _engine.GroupAggResult:
    """Deprecated: use ``repro.query.Query(ops=(op,), window=Window(ws, wa))``
    + ``execute``."""
    _engine._deprecated("repro.core.swag",
                        "Query(ops=(op,), window=Window(ws, wa))")
    if op == "median":
        raise ValueError("op='median' is not a combiner — use swag_median "
                         "(or swag_panes, which returns a MedianResult)")
    from repro import query as _q
    name = op.name if isinstance(op, Combiner) else _q.canonical_op(op)
    q = _q.Query(ops=(op,), window=_q.Window(ws=ws, wa=wa, panes=panes),
                 presorted=presorted)
    res, _ = _q.execute(q, groups, keys, backend="reference",
                        use_xla_sort=use_xla_sort)
    return _engine.GroupAggResult(res.groups, res.values[name], res.valid,
                                  res.num_groups)


def _sort_panes(groups: Array, keys: Array, *, ws: int, wa: int,
                use_xla_sort: bool) -> tuple[Array, Array, int, int]:
    """Frame + sort each pane once by (group, key). Returns (pg, pk, nw, p)."""
    n = groups.shape[-1]
    p = ws // wa
    nw = num_windows(n, ws, wa)
    np_ = nw + p - 1  # panes that participate in at least one window
    pg = frame_panes(groups, wa, np_)
    pk = frame_panes(keys, wa, np_)
    srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs
    pg, pk = jax.vmap(lambda g, k: srt(g, k, full_width=True))(pg, pk)
    return pg, pk, nw, p


def swag_panes(groups: Array, keys: Array, *, ws: int, wa: int, op="sum",
               use_xla_sort: bool = False, interpolate: bool = False):
    """Pane-based SWAG: sort each WA-pane once, share it across the P = WS/WA
    windows containing it.

    ``op`` may be any registered combiner name, a :class:`Combiner`, or
    ``"median"`` (returns :class:`MedianResult`; ``interpolate`` applies to
    median only).  Requires :func:`pane_compatible` ``(ws, wa)`` or
    ``wa == ws``, and at least one full window.  Element-exact vs. the
    re-sort path (see module docstring).
    """
    resolve_panes(ws, wa, groups.shape[-1], True)  # validate or raise

    pg, pk, nw, p = _sort_panes(groups, keys, ws=ws, wa=wa,
                                use_xla_sort=use_xla_sort)

    def merged_windows(tail):
        """Assemble each window from its P presorted panes (bitonic merge
        when P > 1 — a no-op for tumbling windows) and apply ``tail``."""
        wg = _pane_windows(pg, nw, p)
        wk = _pane_windows(pk, nw, p)

        def per_window(g, k):
            if p > 1:
                g, k = sorter.merge_presorted((g, k), run=wa, num_keys=2)
            return tail(g, k)

        return jax.vmap(per_window)(wg, wk)

    if op == "median":
        return merged_windows(
            lambda g, k: _median_sorted_window(g, k, interpolate=interpolate))

    # float sums are kept on the merge path: combining per-pane partial sums
    # reorders float additions (~ulp drift), while the merged window is the
    # *identical* sequence the re-sort path feeds the engine — bit-exact.
    reorder_sensitive = (op == "sum"
                         and jnp.issubdtype(keys.dtype, jnp.floating))
    if (isinstance(op, str) and op in PARTIAL_OPS and p > 1
            and not reorder_sensitive):
        return _swag_shared_partials(pg, pk, nw=nw, p=p, wa=wa, op=op)

    return merged_windows(lambda g, k: _engine._group_by_aggregate(g, k, op))


def _partial_combiner(comb: Combiner) -> Combiner:
    """Combine already-aggregated per-pane partial values: the table-level
    view from :func:`repro.core.combiners.partial_combiner` (identity lift,
    fold with ``merge_partial``).  Valid here because PARTIAL_OPS states are
    single arrays with identity finalize."""
    return _mk_partial_combiner(comb)


def _swag_shared_partials(pg: Array, pk: Array, *, nw: int, p: int, wa: int,
                          op: str) -> _engine.GroupAggResult:
    """The incremental fast path: one engine pass per pane, then per window a
    group-only merge of P compacted partial runs + one combining engine pass.

    Each pane's :class:`GroupAggResult` is an ascending run of *unique* group
    ids (PAD_GROUP tail), so the P runs merge with the bitonic merge network
    — partial values of one group meet as one segment and the identity-lift
    combiner folds them with ``comb.op``.  The merge compares the full
    (group, value) pair: group alone would suffice semantically (PARTIAL_OPS
    are commutative) and unique-per-run groups keep every run
    (group, value)-ascending anyway, but a key-only merge carrying the
    values as pure *payload* has been observed to trigger a minutes-long
    XLA:CPU compile (jax 0.4.37), so the values join the comparison instead.
    """
    comb = get_combiner(op)
    partial = jax.vmap(
        lambda g, k: _engine._group_by_aggregate(g, k, op))(pg, pk)

    wg = _pane_windows(partial.groups, nw, p)   # [NW, P*WA]
    wv = _pane_windows(partial.values, nw, p)
    widx = jnp.arange(nw)[:, None] + jnp.arange(p)[None, :]
    n_valid = jnp.sum(partial.num_groups[widx], axis=-1)  # [NW]

    pcomb = _partial_combiner(comb)

    def per_window(g, v, nv):
        g, v = sorter.merge_presorted((g, v), run=wa, num_keys=2)
        return _engine._group_by_aggregate(g, v, pcomb, n_valid=nv)

    return jax.vmap(per_window)(wg, wv, n_valid)


class MedianResult(NamedTuple):
    groups: Array   # [num_windows, WS]
    medians: Array  # [num_windows, WS] (float32 if interpolate else key dtype)
    valid: Array    # [num_windows, WS]
    num_groups: Array  # [num_windows]


def _median_sorted_window(g: Array, k: Array, *, interpolate: bool,
                          n_valid: Array | None = None) -> MedianResult:
    """Median per group of one closed, (group, key)-sorted window.

    The sorter output is consumed *with* group cardinalities (paper: "append
    the median-related information such as group cardinality alongside the
    data"): counts + group start offsets come from one engine pass and the
    middle element(s) of each group's sorted run are picked out.

    Also serves grouped median *without* a window (``n_valid`` marks the
    real prefix; the padding tail forms its own never-emitted segment).
    """
    counts = _engine._group_by_aggregate(g, k, "count", n_valid=n_valid)
    if n_valid is not None:
        g = jnp.where(jnp.arange(g.shape[0]) < n_valid, g,
                      _engine.PAD_GROUP)
    n = g.shape[0]
    starts = segscan.segment_starts(g)
    seg_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    # start_pos[j] = index of first element of group j (scatter-min onto
    # an identity-filled buffer)
    start_pos = jnp.full((n,), n, jnp.int32).at[seg_id].min(
        jnp.arange(n, dtype=jnp.int32), mode="drop",
        indices_are_sorted=True)
    cnt = counts.values.astype(jnp.int32)
    lo_idx = start_pos + jnp.maximum(cnt - 1, 0) // 2
    hi_idx = start_pos + cnt // 2
    lo = k[jnp.clip(lo_idx, 0, n - 1)]
    hi = k[jnp.clip(hi_idx, 0, n - 1)]
    if interpolate:
        med = (lo.astype(jnp.float32) + hi.astype(jnp.float32)) / 2.0
    else:
        med = lo  # lower median (stays in the key domain)
    return MedianResult(counts.groups, med, counts.valid, counts.num_groups)


def _swag_median(groups: Array, keys: Array, *, ws: int, wa: int,
                 interpolate: bool = False, use_xla_sort: bool = False,
                 panes: bool | None = None) -> MedianResult:
    """Internal (non-deprecated) median per group per window — the paper's
    non-incremental example.

    Median has no incremental combiner, so the pane path (``panes=None``
    auto-dispatch, same rules as :func:`_swag`) keeps it *exact* by merging
    the presorted panes into the fully sorted window before the rank pick.
    """
    if resolve_panes(ws, wa, groups.shape[-1], panes):
        return swag_panes(groups, keys, ws=ws, wa=wa, op="median",
                          use_xla_sort=use_xla_sort, interpolate=interpolate)

    gw = frame_windows(groups, ws, wa)
    kw = frame_windows(keys, ws, wa)

    def per_window(g, k):
        srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs
        g, k = srt(g, k, full_width=True)
        return _median_sorted_window(g, k, interpolate=interpolate)

    return jax.vmap(per_window)(gw, kw)


def swag_median(groups: Array, keys: Array, *, ws: int, wa: int,
                interpolate: bool = False, use_xla_sort: bool = False,
                panes: bool | None = None) -> MedianResult:
    """Deprecated: use ``repro.query.Query(ops=("median",),
    window=Window(ws, wa), interpolate=...)`` + ``execute``."""
    _engine._deprecated(
        "repro.core.swag_median",
        'Query(ops=("median",), window=Window(ws, wa))')
    from repro import query as _q
    q = _q.Query(ops=("median",), window=_q.Window(ws=ws, wa=wa, panes=panes),
                 interpolate=interpolate)
    res, _ = _q.execute(q, groups, keys, backend="reference",
                        use_xla_sort=use_xla_sort)
    return MedianResult(res.groups, res.values["median"], res.valid,
                        res.num_groups)


def per_group_chunk_scan(spec, state, groups: Array, keys: Array, emit,
                         counters=None):
    """Thread a pane store over WA-sized stream chunks: push each chunk,
    then apply ``emit`` to the updated store (one evaluation per chunk).
    The trailing remainder (< WA tuples) stays unpushed — mirror of
    :func:`frame_panes`.  Returns ``(final_state, stacked emissions)``;
    with ``counters`` (an :mod:`repro.obs.counters` dict) ``(final_state,
    stacked emissions, counters)``, the store's evictions and occupancy
    high-water mark over every tuple."""
    ne = groups.shape[-1] // spec.wa
    with stage("frame"):
        gc = frame_panes(groups.astype(jnp.int32), spec.wa, ne)
        kc = frame_panes(keys, spec.wa, ne)

    if counters is not None:
        from repro.obs import counters as _c
        counters = _c.ensure(counters,
                             ("pane_evictions", "pane_occupancy_hwm"))

    def step(carry, x):
        st, cnt = carry
        g, k = x
        if cnt is None:
            st = _panestore.push(spec, st, g, k)
        else:
            st, cnt = _panestore.push(spec, st, g, k, counters=cnt)
        return (st, cnt), emit(st)

    with stage("store_push"):
        (state, counters), out = jax.lax.scan(step, (state, counters),
                                              (gc, kc))
    if counters is None:
        return state, out
    return state, out, counters


def _group_ranks(groups: Array):
    """Within-group arrival rank of every tuple, plus the stable group-sort
    permutation — vectorised, no per-tuple scan.  ``order`` sorts the
    stream by group id with arrival order preserved inside each group, so
    the tuple at sorted position ``i`` has rank ``i - start_of_its_group``
    (segment starts recovered by a running max over start positions)."""
    n = groups.shape[-1]
    order = jnp.argsort(groups, stable=True).astype(jnp.int32)
    sg = groups[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), sg[1:] != sg[:-1]]) if n else \
        jnp.zeros((0,), bool)
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(starts, pos, 0))
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(pos - seg_start)
    return ranks, order, sg


def _pergroup_dir_scan(spec, gc: Array, rc: Array, with_counters: bool):
    """Directory-only push scan for the batched per-group path: thread just
    the ``[C]`` bookkeeping columns (owner/count/base/stamp/clock) plus an
    ``abase`` column through every tuple — never the ``[C, WA]`` ring
    buffers — and emit one directory snapshot per WA chunk.

    ``abase[s]`` is the **arrival rank** (within-group cumulative tuple
    count) of slot ``s``'s first tuple.  The store's own ``base`` is a
    store-local seq that resets to 0 when a group's panes are all evicted;
    arrival ranks never reset, so windows derived from ``abase`` map 1:1
    onto positions in the group-sorted stream across eviction epochs.
    Placement decisions still use the store-seq ``base`` via the shared
    :func:`repro.core.panestore._push_decide` — identical policy to the
    reference scan by construction.

    Returns ``(carry, (owner, abase, count) snapshots [NE, C])`` where
    ``carry`` is ``(owner, count, base, abase, stamp, clock[, evictions,
    occupancy high-water mark])`` (the counters ride only when
    ``with_counters``)."""
    c = spec.capacity
    init = (jnp.full((c,), _panestore.PAD_GROUP, jnp.int32),   # owner
            jnp.zeros((c,), jnp.int32),                        # count
            jnp.zeros((c,), jnp.int32),                        # base
            jnp.zeros((c,), jnp.int32),                        # abase
            jnp.full((c,), -1, jnp.int32),                     # stamp
            jnp.zeros((), jnp.int32))                          # clock
    if with_counters:
        init = init + (jnp.zeros((), jnp.int32),               # evictions
                       jnp.zeros((), jnp.int32))               # occupancy

    def tup(carry, x):
        owner, count, base, abase, stamp, clock = carry[:6]
        g, r = x
        (owner, count, base, stamp, clock), slot, _lane, _m, alloc, \
            _closes, evicted = _panestore._push_decide(
                spec, owner, count, base, stamp, clock, g, True)
        abase = abase.at[slot].set(jnp.where(alloc, r, abase[slot]))
        out = (owner, count, base, abase, stamp, clock)
        if with_counters:
            out = out + _count_push(carry[6:], owner, evicted)
        return out, None

    def chunk(carry, x):
        carry, _ = jax.lax.scan(tup, carry, x)
        return carry, (carry[0], carry[3], carry[1])

    return jax.lax.scan(chunk, init, (gc, rc))


def _count_push(counts, owner: Array, evicted: Array):
    """``(evictions, occupancy high-water mark)`` after one tuple's push,
    from the directory's new ``owner`` column."""
    ev, hwm = counts
    occupied = jnp.sum((owner != _panestore.PAD_GROUP).astype(jnp.int32))
    return ev + evicted.astype(jnp.int32), jnp.maximum(hwm, occupied)


def _snapshot_directory(own_s: Array):
    """Vectorised slot directory over ``[NE, C]`` owner snapshots: the
    unique live group ids per evaluation (ascending, PAD tail) and their
    count — the batched form of the dedupe in
    :func:`repro.core.panestore._slot_directory`."""
    ne, c = own_s.shape
    pad = _panestore.PAD_GROUP
    so = jnp.sort(own_s, axis=1)
    occupied = so != pad
    prev = jnp.concatenate(
        [jnp.full((ne, 1), pad, jnp.int32), so[:, :-1]], axis=1)
    firsts = occupied & ((so != prev) | (jnp.arange(c)[None, :] == 0))
    num = jnp.sum(firsts.astype(jnp.int32), axis=1)
    rank = jnp.cumsum(firsts.astype(jnp.int32), axis=1) \
        - firsts.astype(jnp.int32)
    scatter = jnp.where(firsts, rank, c)
    ugroups = jax.vmap(
        lambda s, v: jnp.full((c + 1,), pad, jnp.int32).at[s].set(
            v, mode="drop")[:c])(scatter, so)
    return ugroups, num


def _pergroup_eval_windows(spec, own_s: Array, ab_s: Array, cnt_s: Array):
    """Per-(evaluation, group-row) window bounds in **arrival-rank** units:
    for each unique group of each snapshot, ``m`` is its arrival count and
    ``lo = max(m - ws_g, amin)`` where ``amin`` is the arrival rank of the
    oldest retained pane — eviction truncates the window, which is exactly
    the paper's approximation knob showing up as a raised lower bound.
    Returns ``(ugroups, num, valid, lo, m)``, all ``[NE, C]`` but ``num``.
    """
    pad = _panestore.PAD_GROUP
    c = own_s.shape[1]
    imin = jnp.iinfo(jnp.int32).min
    imax = jnp.iinfo(jnp.int32).max
    ugroups, num = _snapshot_directory(own_s)
    occ = own_s != pad                                        # [NE, C]
    samem = ((ugroups[:, :, None] == own_s[:, None, :]) & occ[:, None, :]
             & (ugroups[:, :, None] != pad))                  # [NE, R, S]
    span = ab_s + cnt_s                                       # [NE, S]
    m = jnp.max(jnp.where(samem, span[:, None, :], imin), axis=2)
    amin = jnp.min(jnp.where(samem, ab_s[:, None, :], imax), axis=2)
    valid = jnp.arange(c)[None, :] < num[:, None]
    lo = jnp.maximum(m - spec.ws_of(ugroups), amin)
    return ugroups, num, valid, jnp.where(valid, lo, 0), \
        jnp.where(valid, m, 0)


def _sparse_table(x: Array, combine, sentinel):
    """Range-query sparse table levels: ``t[l][i] = combine over
    x[i : i + 2**l]`` (sentinel-padded past the end).  O(N log N) build,
    O(1) per range query."""
    n = x.shape[-1]
    t = [x]
    step = 1
    while step < n:
        cur = t[-1]
        shifted = jnp.concatenate(
            [cur[step:], jnp.full((step,), sentinel, cur.dtype)])[:n]
        t.append(combine(cur, shifted))
        step *= 2
    return jnp.stack(t)


def _sparse_query(table: Array, a: Array, length: Array, combine):
    """``combine`` over ``x[a : a + length]`` (``length >= 1``) as two
    overlapping power-of-two blocks; floor-log2 via count-leading-zeros
    (exact, unlike a float log)."""
    n = table.shape[-1]
    length = jnp.maximum(length, 1)
    lev = 31 - jax.lax.clz(length)
    blk = jnp.left_shift(1, lev)
    a1 = jnp.clip(a, 0, n - 1)
    a2 = jnp.clip(a + length - blk, 0, n - 1)
    return combine(table[lev, a1], table[lev, a2])


def _pergroup_partial_values(spec, names, sk: Array, sg: Array,
                             ugroups: Array, lo: Array, m: Array,
                             valid: Array):
    """Tuple-centric batched evaluation of the partial-path ops: each
    (evaluation, group) window is the contiguous slice
    ``[off_g + lo, off_g + m)`` of the group-sorted stream, so sums come
    from one prefix sum (int wraparound cancels in the difference),
    min/max from one sparse table, count from the bounds — O(1) per window
    after O(N log N) shared prep, vs one gather + merge replay per window.
    """
    key_dtype = sk.dtype
    n = sk.shape[-1]
    off = jnp.searchsorted(sg, ugroups, side="left").astype(jnp.int32)
    a = jnp.clip(off + lo, 0, n)
    b = jnp.clip(off + m, 0, n)
    cnt = jnp.where(valid, m - lo, 0)
    rsum = None
    if any(nm in ("sum", "mean") for nm in names):
        acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
        ps = jnp.concatenate([jnp.zeros((1,), acc),
                              jnp.cumsum(sk.astype(acc))])
        rsum = jnp.where(valid, ps[b] - ps[a], jnp.zeros((), acc))
    out = {}
    for nm in names:
        if nm == "count":
            out[nm] = cnt
        elif nm == "sum":
            out[nm] = rsum
        elif nm == "mean":
            out[nm] = (rsum.astype(jnp.float32)
                       / jnp.maximum(cnt, 1).astype(jnp.float32))
        elif nm == "min":
            hi = _panestore._key_sentinel(key_dtype)
            tbl = _sparse_table(jnp.asarray(sk), jnp.minimum, hi)
            v = _sparse_query(tbl, a, b - a, jnp.minimum)
            out[nm] = jnp.where(cnt > 0, v,
                                jnp.zeros((), key_dtype)).astype(key_dtype)
        elif nm == "max":
            lo_s = (jnp.iinfo(key_dtype).min
                    if jnp.issubdtype(key_dtype, jnp.integer) else -jnp.inf)
            tbl = _sparse_table(jnp.asarray(sk), jnp.maximum, lo_s)
            v = _sparse_query(tbl, a, b - a, jnp.maximum)
            out[nm] = jnp.where(cnt > 0, v,
                                jnp.zeros((), key_dtype)).astype(key_dtype)
        else:  # pragma: no cover - guarded by partial_path_names
            raise ValueError(f"{nm} is not a partial-path op")
    return out


def _reconstruct_store(spec, carry, sg: Array, sk: Array):
    """Rebuild the ``[C, WA]`` ring buffers the directory-only scan never
    materialised: lane ``l`` of an occupied slot holds the key at position
    ``off(owner) + abase + l`` of the group-sorted stream with seq
    ``base + l``, and closed panes re-apply the stable sort-at-close.
    Freed slots keep init contents (their bytes are dead — the directory
    masks them everywhere).  The result is a valid continuation state:
    further pushes behave exactly as under the reference scan."""
    owner, count, base, abase, stamp, clock = carry[:6]
    wa = spec.wa
    n = sg.shape[-1]
    occ = owner != _panestore.PAD_GROUP
    off = jnp.searchsorted(sg, owner, side="left").astype(jnp.int32)
    lanes = jnp.arange(wa)[None, :]
    fill = occ[:, None] & (lanes < count[:, None])
    pos = jnp.clip(off[:, None] + abase[:, None] + lanes, 0,
                   max(n - 1, 0))
    keys = jnp.where(fill, sk[pos], jnp.zeros((), sk.dtype))
    seqs = jnp.where(fill, base[:, None] + lanes, 0)
    order = jnp.argsort(keys, axis=-1, stable=True)
    closed = (count == wa)[:, None]
    keys = jnp.where(closed, jnp.take_along_axis(keys, order, axis=-1),
                     keys)
    seqs = jnp.where(closed, jnp.take_along_axis(seqs, order, axis=-1),
                     seqs)
    return _panestore.PaneStoreState(owner, keys, seqs, count, base,
                                     stamp, clock)


def _plan_scan(spec, gc: Array, with_counters: bool):
    """The store-placement plan by one :func:`_push_decide` step per tuple:
    the per-tuple oracle of :func:`pergroup_write_plan`, which takes it for
    any push whose panes do not fit the store (the evicting regime).

    Returns ``(slots, lanes, seqs [NE, WA]; own_s, cnt_s, base_s [NE, C])``
    and, ``with_counters``, the evictions and occupancy high-water mark
    after them."""
    c = spec.capacity
    init = (jnp.full((c,), _panestore.PAD_GROUP, jnp.int32),
            jnp.zeros((c,), jnp.int32), jnp.zeros((c,), jnp.int32),
            jnp.full((c,), -1, jnp.int32), jnp.zeros((), jnp.int32))
    if with_counters:
        init = init + (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    def tup(carry, g):
        directory, slot, lane, m_g, _alloc, _closes, evicted = \
            _panestore._push_decide(spec, *carry[:5], g, True)
        if with_counters:
            directory = directory + _count_push(carry[5:], directory[0],
                                                evicted)
        return directory, (slot, lane, m_g)

    def chunk(carry, g):
        carry, (slot, lane, seq) = jax.lax.scan(tup, carry, g)
        owner, count, base = carry[:3]
        return carry, (slot, lane, seq, owner, count, base)

    carry, out = jax.lax.scan(chunk, init, gc)
    return out + tuple(carry[5:])


def _rows_cumulative(op, x: Array) -> Array:
    """Inclusive running ``op`` (``jnp.maximum`` or ``jnp.add``) over
    ``x [R, W]`` in row-major order: within rows along the major axis of the
    transpose, then each row's prefix of the rows before it (both
    cumulative reductions run down a major axis, which the TPU compiler
    builds far faster than one down a long minor axis)."""
    cum = jax.lax.cummax if op is jnp.maximum else jax.lax.cumsum
    within = cum(x.T, axis=0).T
    last = within[:, -1]
    before = jnp.concatenate([jnp.zeros((1,), x.dtype), cum(last)[:-1]])
    return op(within, before[:, None])


def _plan_ranks(spec, gc: Array, with_counters: bool):
    """Everything of the allocation plan that needs no sequential step.

    A stable group sort gives every tuple its within-group arrival rank
    ``r``; while no allocation finds the store full, ``r`` is exactly the
    seq :func:`_push_decide` gives it (no eviction, so no group's count
    resets, and a group's newest pane never retires on its own push).  So
    the tuple writes lane ``r % WA`` of its group's pane ``r // WA``, a
    pane is allocated at its rank-``k WA`` tuple, and it retires at the
    group's tuple of rank ``k WA + WA + ws_g - 1`` (the first at which
    ``base + WA <= m - ws_g``), if the push holds that many.

    Per tuple this costs one sort, one scatter and two gathers; the rest
    is per pane (``A = min(N, NE + C)`` entries, ``count`` of them used:
    allocations in arrival order).  Returns ``(fits, hwm, rank [NE, WA],
    pane [N], alloc_t, owner, base, spane, ret [A], count)``: whether every
    allocation finds a free slot (no eviction, no ``PAD_GROUP`` tuple);
    the occupancy high-water mark over the push's tuples
    (``with_counters``, else None); each tuple's rank and its pane's
    index in group-sorted order; and per allocation its arrival index,
    group, base, index in group-sorted order and the arrival index of the
    tuple that retires its pane (``N`` when none does in this push)."""
    ne, wa = gc.shape
    n = ne * wa
    # when the panes fit, allocations <= C + retirements, and a retired
    # pane is a full one, so retirements <= NE
    a = min(n, ne + spec.capacity)
    g = gc.reshape(n)
    pos = jnp.arange(n, dtype=jnp.int32)
    sg, order = jax.lax.sort((g, pos), num_keys=1, is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), bool), sg[1:] != sg[:-1]])
    seg = _rows_cumulative(jnp.maximum,
                           jnp.where(first, pos, 0).reshape(ne, wa)).reshape(n)
    srank = pos - seg
    spane = _rows_cumulative(jnp.add, ((srank & (wa - 1)) == 0).astype(
        jnp.int32).reshape(ne, wa)).reshape(n) - 1
    inv = jnp.zeros((n,), jnp.int32).at[order].set(pos, unique_indices=True)
    rank = srank[inv]

    alloc = (rank & (wa - 1)) == 0
    past = rank - (wa + spec.ws_of(g) - 1)
    retire = (past >= 0) & ((past & (wa - 1)) == 0)
    na = _rows_cumulative(jnp.add, alloc.astype(jnp.int32).reshape(ne, wa))
    nr = _rows_cumulative(jnp.add, retire.astype(jnp.int32).reshape(ne, wa))
    live = (na - nr).reshape(n)
    # occupancy the moment each allocation is placed: this tuple's
    # retirement comes after its allocation
    peak = jnp.max(jnp.where(alloc, live + retire, 0))
    fits = (peak <= spec.capacity) & ~jnp.any(g == _panestore.PAD_GROUP)
    hwm = jnp.max(live) if with_counters else None

    alloc_t = jnp.full((a,), n, jnp.int32).at[
        jnp.where(alloc, na.reshape(n) - 1, a)].set(pos, mode="drop")
    t = jnp.minimum(alloc_t, n - 1)
    p = inv[t]
    q = jnp.minimum(p + wa + spec.ws_of(sg[p]) - 1, n - 1)
    ret = jnp.where((alloc_t < n) & (p + wa + spec.ws_of(sg[p]) - 1 < n)
                    & (seg[q] == seg[p]), order[q], n)
    return (fits, hwm, rank.reshape(ne, wa), spane[inv], alloc_t, sg[p],
            srank[p], spane[p], ret, na.reshape(n)[-1])


def _place_allocations(c: int, alloc_t: Array, alloc_ret: Array,
                       count: Array) -> Array:
    """The slot of each of the first ``count`` allocations, in order."""
    def place(j, carry):
        release, slot_of = carry
        s = jnp.argmax(release < alloc_t[j]).astype(jnp.int32)
        return release.at[s].set(alloc_ret[j]), slot_of.at[j].set(s)

    return jax.lax.fori_loop(
        0, count, place, (jnp.full((c,), -1, jnp.int32),
                          jnp.zeros(alloc_t.shape, jnp.int32)))[1]


def _plan_by_allocation(spec, rank: Array, pane: Array, alloc_t: Array,
                        owner: Array, base: Array, spane: Array,
                        ret: Array, count: Array):
    """The plan of a push whose panes fit, in one sequential step per pane
    allocation: each takes the lowest free slot (``_push_decide``'s
    ``argmax(free)``).  The loop carries, per slot, the arrival index of
    the tuple that retires its pane, so a slot is free for an allocation
    once that index lies strictly before it (a tuple allocates before it
    retires), and no step applies retirements.

    Then, vectorised, every tuple's slot and each chunk's directory
    snapshot.  A slot's owner, base and retiring tuple after chunk ``e``
    are those of the last pane placed there by then: a running sum down
    the chunks of each placement's change from the pane before it in the
    slot.  The pane is live unless it has retired by then, and its count
    is the slot's writes so far less ``WA`` for each pane before it (a
    retired pane is a full one).  Returns what :func:`_plan_scan` does,
    counters aside."""
    ne, wa = rank.shape
    n = ne * wa
    c = spec.capacity
    a = alloc_t.shape[0]
    slot_of = _place_allocations(c, alloc_t, ret, count)
    j = jnp.arange(a, dtype=jnp.int32)
    placed = j < count
    slots = jnp.zeros((a,), jnp.int32).at[jnp.where(placed, spane, a)].set(
        slot_of, mode="drop")[pane].reshape(ne, wa)

    # the pane placed in the same slot before each one
    key, jj = jax.lax.sort((jnp.where(placed, slot_of, c), j), num_keys=2)
    same = jnp.concatenate([jnp.zeros((1,), bool), key[1:] == key[:-1]])
    before = jnp.zeros((a,), jnp.int32).at[jj].set(
        jnp.where(same, jnp.roll(jj, 1), a), unique_indices=True)
    cell = jnp.where(placed, (alloc_t // wa) * c + slot_of, ne * c)

    def down_chunks(x):
        """Running sum down the chunks of ``x`` added at each placement."""
        return jax.lax.cumsum(jnp.zeros((ne * c,), x.dtype).at[cell].add(
            x, mode="drop").reshape(ne, c), axis=0)

    def change(x):
        return x - jnp.where(before < a, x[jnp.minimum(before, a - 1)], 0)

    panes = down_chunks(jnp.ones((a,), jnp.int32))
    writes = jax.lax.cumsum(jnp.sum(
        (slots[:, :, None] == jnp.arange(c)[None, None, :]).astype(
            jnp.int32), axis=1), axis=0)
    ends = (jnp.arange(ne, dtype=jnp.int32)[:, None] + 1) * wa
    live = (panes > 0) & (down_chunks(change(ret)) >= ends)
    own_s = jnp.where(live, down_chunks(change(owner)),
                      _panestore.PAD_GROUP)
    cnt_s = jnp.where(live, writes - wa * (panes - 1), 0)
    base_s = jnp.where(live, down_chunks(change(base)), 0)
    return (slots, rank & (wa - 1), rank, own_s, cnt_s, base_s)


def _plan_snapshot(spec, slots: Array, lanes: Array, seqs: Array,
                   own_s: Array, cnt_s: Array, base_s: Array):
    """The write plan from a directory plan: each chunk's written slots,
    close-sort mask, per-slot staleness bound and unique groups (see
    :func:`pergroup_write_plan`)."""
    c = spec.capacity
    pad = _panestore.PAD_GROUP
    imin = jnp.iinfo(jnp.int32).min
    with stage("dir_snapshot"):
        written = jnp.any(
            slots[:, :, None] == jnp.arange(c)[None, None, :], axis=1)
        sortmask = (cnt_s == spec.wa) & written
        occ = own_s != pad
        span = jnp.where(occ, base_s + cnt_s, imin)
        samem = (occ[:, :, None] & (own_s[:, :, None] == own_s[:, None, :])
                 & occ[:, None, :])
        m = jnp.max(jnp.where(samem, span[:, None, :], imin), axis=2)
        lo_s = jnp.where(occ, m - spec.ws_of(own_s), 0)
        ugroups, num = _snapshot_directory(own_s)
    return (slots, lanes, seqs, own_s, cnt_s, lo_s, sortmask, ugroups, num)


def pergroup_write_plan(spec, groups: Array, counters=None):
    """Everything the fused Pallas replay kernel needs, precomputed in XLA
    ("store bookkeeping in XLA", as with the gather path): per-tuple write
    coordinates into the VMEM-resident ring buffers, per-chunk directory
    snapshots with per-slot staleness bounds, the close-sort mask, and the
    per-evaluation group directory.

    The directory takes one sequential step per pane allocation
    (:func:`_plan_ranks`, :func:`_plan_by_allocation`) when every
    allocation of the push finds a free slot, and otherwise the per-tuple
    scan (:func:`_plan_scan`), which alone models eviction; both give the
    same plan wherever the first applies.

    Returns ``(slots, lanes, seqs [NE, WA]; own_s, cnt_s, lo_s, sortmask
    [NE, C]; ugroups [NE, C], num [NE])`` — seq/lo in store-seq units (the
    kernel masks within one epoch; freed slots are masked by ``own_s``).
    With ``counters`` (an :mod:`repro.obs.counters` dict) returns ``(that
    tuple, counters)``: the store's evictions and occupancy high-water
    mark over every tuple, and ``pane_dir_fallbacks``, 1 when the push
    took the per-tuple scan.
    """
    ne = groups.shape[-1] // spec.wa
    stats = counters is not None
    with stage("frame"):
        gc = frame_panes(jnp.asarray(groups, jnp.int32), spec.wa, ne)

    with stage("dir_scan"):
        if spec.is_time or ne == 0:
            plan = _plan_scan(spec, gc, stats)
            fell_back = jnp.ones((), jnp.int32)
        else:
            fits, hwm, *ranked = _plan_ranks(spec, gc, stats)

            def by_allocation(ranked):
                out = _plan_by_allocation(spec, *ranked)
                return out + ((jnp.zeros((), jnp.int32), hwm) if stats
                              else ())

            plan = jax.lax.cond(fits, by_allocation,
                                lambda _: _plan_scan(spec, gc, stats),
                                tuple(ranked))
            # keep XLA from sinking the snapshot's [NE, C, C] broadcasts
            # into both branches, where they would be materialised
            plan = jax.lax.optimization_barrier(plan)
            fell_back = (~fits).astype(jnp.int32)
    out = _plan_snapshot(spec, *plan[:6])
    if counters is None:
        return out
    from repro.obs import counters as _c
    counters = _c.bump(counters, "pane_evictions", plan[6])
    counters = _c.high_water(counters, "pane_occupancy_hwm", plan[7])
    counters = _c.bump(counters, "pane_dir_fallbacks", fell_back)
    return out, counters


def swag_per_group(groups: Array, keys: Array, *, spec, ops,
                   interpolate: bool = False, state=None, counters=None):
    """Per-group-window SWAG on the shared pane store (the paper's
    approximation for SWAG with per-group windows) — batch entry.

    The stream is cut into ``spec.wa``-sized chunks; after each chunk one
    **evaluation** replays every live group's last ``WS_g`` own tuples from
    the store (``spec`` is a :class:`repro.core.panestore.PaneStoreSpec`).
    Unlike the global-window paths, the window of group ``g`` counts only
    ``g``'s tuples — there is no single stream-level WS, so evaluations
    start with the first chunk.

    Two batched regimes replace the historical one-replay-per-chunk scan:

    * **partial path** (every op in
      :data:`repro.core.panestore.PANE_PARTIAL_OPS`; float keys keep
      sum/mean off it): a directory-only scan derives per-chunk window
      bounds in arrival-rank units and all NE x C windows are evaluated at
      once from the group-sorted stream (prefix sums / sparse tables) —
      the ring buffers are reconstructed once at the end, never pushed
      per chunk.
    * **merge path** (median/distinct_count, engine-tail combiners, float
      sum/mean, or a continued stream via ``state=``): the push scan emits
      gathered runs per chunk, and ONE batched merge+tails pass evaluates
      all NE x C replay rows after the scan instead of NE separate merges
      inside it.  Any merge op present routes *all* ops through the merge
      pass (one launch, and the same rows serve every op).

    Both regimes are bit-exact vs the per-chunk reference (identical
    placement policy through the shared ``_push_decide``; identical tail
    formulas).  With ``counters`` (an :mod:`repro.obs.counters` dict)
    returns ``(out, state, counters)``.

    Returns ``((groups, values, valid, num_groups), final_state)`` with a
    leading ``[num_evals = N // WA]`` axis and ``spec.capacity`` output
    slots per evaluation; ``state=None`` starts a fresh store (pass the
    previous state to continue a stream).
    """
    names = [op.name if isinstance(op, Combiner) else op for op in ops]
    keys = jnp.asarray(keys)
    groups = jnp.asarray(groups, jnp.int32)
    ne = groups.shape[-1] // spec.wa
    psel = ([] if spec.is_time
            else _panestore.partial_path_names(names, keys.dtype))
    all_partial = bool(psel) and all(psel)

    if all_partial and state is None and ne > 0:
        ranks, order, sg = _group_ranks(groups)
        sk = keys[order]
        gc = frame_panes(groups, spec.wa, ne)
        rc = frame_panes(ranks, spec.wa, ne)
        carry, (own_s, ab_s, cnt_s) = _pergroup_dir_scan(
            spec, gc, rc, counters is not None)
        ugroups, num, valid, lo, m = _pergroup_eval_windows(
            spec, own_s, ab_s, cnt_s)
        values = _pergroup_partial_values(spec, names, sk, sg, ugroups,
                                          lo, m, valid)
        values = {nm: jnp.where(valid, v, jnp.zeros((), v.dtype))
                  for nm, v in values.items()}
        final = _reconstruct_store(spec, carry, sg, sk)
        out = (ugroups, values, valid, num)
        if counters is None:
            return out, final
        from repro.obs import counters as _c
        counters = _c.bump(counters, "pane_evictions", carry[6])
        counters = _c.high_water(counters, "pane_occupancy_hwm", carry[7])
        return out, final, counters

    if state is None:
        state = _panestore.init_store(spec, keys.dtype)
    scanned = per_group_chunk_scan(
        spec, state, groups, keys.astype(state.keys.dtype),
        lambda st: _panestore.gather_runs(spec, st), counters=counters)
    if counters is None:
        state, runs = scanned
    else:
        state, runs, counters = scanned

    c = spec.capacity
    length = runs.run_keys.shape[-1]
    mvals, _cnts = _panestore.replay_rows(
        spec, runs.run_keys.reshape(ne * c, length),
        runs.run_valid.reshape(ne * c, length),
        list(ops), names, key_dtype=state.keys.dtype,
        interpolate=interpolate)
    valid = jnp.arange(c)[None, :] < runs.num_groups[:, None]
    values = {nm: jnp.where(valid, v.reshape(ne, c),
                            jnp.zeros((), v.dtype))
              for nm, v in mvals.items()}
    out = (runs.groups, values, valid, runs.num_groups)
    if counters is None:
        return out, state
    return out, state, counters


def window_tails(g: Array, k: Array, pairs, *, interpolate: bool = False):
    """All requested tails over one closed, (group, key)-sorted window — the
    shared dispatch of the re-sort arm, the pane-merge arm and the sharded
    run-merge stage.  Non-median ops share one fused engine pass
    (:func:`engine.multi_engine_step`: segment marks + compaction
    permutation computed once).  ``pairs`` is ``((op, name), ...)``."""
    out = {}
    shared = None
    non_median = tuple(op for op, name in pairs if name != "median")
    if non_median:
        (tg, tvalues, tvalid, tnum), _ = _engine.multi_engine_step(
            g, k, non_median)
        out.update(tvalues)
        shared = (tg, tvalid, tnum)
    if any(name == "median" for _, name in pairs):
        t = _median_sorted_window(g, k, interpolate=interpolate)
        out["median"] = t.medians
        shared = shared or (t.groups, t.valid, t.num_groups)
    return shared[0], out, shared[1], shared[2]


def pane_partials(pane_groups: Array, pane_keys: Array, ops, *,
                  use_xla_sort: bool = False):
    """The local phase of mesh-sharded SWAG, for one ``WA``-wide pane: sort
    the pane once and stop before finalize.

    Returns ``(sorted_groups, sorted_keys, table)`` where ``table`` is the
    pane's per-group :class:`repro.core.engine.PartialTable` over ``ops``
    (may be the empty tuple: run-channel-only queries still need the sorted
    pane).  vmap over the pane axis; each shard of a device mesh runs this
    over its own panes and only the compact tables / sorted runs cross
    devices (`repro.distributed.query_exec`).
    """
    srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs
    g, k = srt(pane_groups, pane_keys, full_width=True)
    table = _engine.multi_engine_partials(g, k, ops)
    return g, k, table


def pane_table_channel(ops, key_dtype, p: int) -> list[bool]:
    """Which ops take the compact per-pane partial-table channel (True) vs
    the merged-sorted-window channel (False) on the pane path.

    ONE predicate shared by the single-device pane dispatch
    (:func:`swag_multi`) and the sharded pane pipeline
    (``repro.distributed.query_exec``) — the sharded path's bit-identical
    guarantee rests on both routing every op the same way.  Incremental
    PARTIAL_OPS keep the table shortcut when panes actually share work
    (``p > 1``); float sums stay on the merge channel (combining per-pane
    partials reorders float additions, ~ulp drift vs the re-sort path).
    """
    reorder_sensitive = jnp.issubdtype(jnp.dtype(key_dtype), jnp.floating)
    return [isinstance(op, str) and op in PARTIAL_OPS and p > 1
            and not (op == "sum" and reorder_sensitive)
            for op in ops]


def swag_multi(groups: Array, keys: Array, *, ws: int, wa: int,
               ops: tuple, interpolate: bool = False,
               presorted: bool = False, use_xla_sort: bool = False,
               panes: bool | None = None):
    """Fused multi-op SWAG: frame + sort (or pane-merge) each window **once**,
    then run every requested combiner tail over the same sorted sequence.

    This is the query planner's reference path for ``len(ops) > 1`` — the
    per-window sort (the dominant cost, ~log^2 WS compare-exchange sweeps) is
    paid once instead of once per operator, and ``"median"`` may ride along
    with incremental ops because the sort-based design hands every tail the
    fully sorted window (the paper's argument for sort-based SWAG).

    Returns ``(out_groups, values, valid, num_groups)`` with a leading
    ``[num_windows]`` axis, where ``values`` maps op name -> value column and
    all columns share ``out_groups``/``valid``/``num_groups``.  Element-exact
    per op vs. the single-op paths (a fully (group, key)-sorted sequence of a
    multiset is unique, so every path feeds identical windows to identical
    tails; incremental ops are exact in either association for the integer /
    min / max / count cases, and float sums take this merge path in the
    single-op code too).
    """
    names = [op.name if isinstance(op, Combiner) else op for op in ops]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate ops in fused SWAG: {names}")

    use_panes = resolve_panes(ws, wa, groups.shape[-1], panes,
                              presorted=presorted)

    def tails(g, k, pairs):
        return window_tails(g, k, pairs, interpolate=interpolate)

    if use_panes:
        pg, pk, nw, p = _sort_panes(groups, keys, ws=ws, wa=wa,
                                    use_xla_sort=use_xla_sort)

        # split ops like the single-op dispatch does: incremental ops keep
        # their shared-partials shortcut (per-pane engine pass + group-only
        # merge of compacted partials), everything else rides the full
        # window merge — and *all* of them share the one pane sort above
        partial_sel = pane_table_channel(ops, keys.dtype, p)
        merge_pairs = tuple((op, name) for (op, name), sel
                            in zip(zip(ops, names), partial_sel) if not sel)

        values: dict = {}
        shared = None
        for op, sel in zip(ops, partial_sel):
            if sel:
                t = _swag_shared_partials(pg, pk, nw=nw, p=p, wa=wa, op=op)
                values[op] = t.values
                shared = shared or (t.groups, t.valid, t.num_groups)

        if merge_pairs:
            wg = _pane_windows(pg, nw, p)
            wk = _pane_windows(pk, nw, p)

            def per_window(g, k):
                if p > 1:
                    g, k = sorter.merge_presorted((g, k), run=wa, num_keys=2)
                return tails(g, k, merge_pairs)

            mg, mvalues, mvalid, mnum = jax.vmap(per_window)(wg, wk)
            values.update(mvalues)
            # prefer the merge arm's layout metadata (identical to the
            # partials arm: same groups per window, ascending, unique)
            shared = (mg, mvalid, mnum)

        return shared[0], values, shared[1], shared[2]

    gw = frame_windows(groups, ws, wa)
    kw = frame_windows(keys, ws, wa)
    all_pairs = tuple(zip(ops, names))

    def per_window(g, k):
        if not presorted:
            srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs
            g, k = srt(g, k, full_width=True)
        return tails(g, k, all_pairs)

    return jax.vmap(per_window)(gw, kw)
