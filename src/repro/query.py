"""The unified query-plan API: one declarative ``Query`` spec, a planner,
and a backend registry — the paper's *adaptability* claim as a library
surface.

The hardware engine is one topology whose behaviour a memory-mapped
``function_select`` register redirects at runtime; this module is the
software analogue.  Instead of picking among scattered entry points
(``group_by_aggregate`` / ``multi_aggregate`` / ``swag`` / ``swag_median`` /
``*_tpu`` wrappers — all still available as deprecated shims), callers
declare *what* they want:

    >>> from repro.query import Query, Window, execute
    >>> q = Query(ops=("sum", "min", "dc"), window=Window(ws=1024, wa=256))
    >>> result, _ = execute(q, groups, keys)
    >>> result.values["sum"].shape      # [num_windows, 1024]

and the planner lowers it onto a backend from
:mod:`repro.kernels.registry` (``reference`` | ``pallas`` |
``pallas-panes`` | ``pallas-panestore`` | ``auto``; overridable per call
or via the ``REPRO_BACKEND`` environment variable).

Per-group windows (the paper's approximation for SWAG with per-group
window sizes) are ``Window(ws_per_group=...)`` — served from the shared,
evicting pane store of :mod:`repro.core.panestore`; streaming windowed
queries thread that store as their carry.

Multi-op queries are **fused**: the sort / pane framing / segment marking /
compaction permutation run once and every requested combiner rides the same
sorted stream — the ``function_select`` register serving N selections at
once.  The single :class:`AggResult` type replaces the per-entry-point
result tuples; all value columns share one ``groups``/``valid`` layout.

``execute(q, ..., mesh=jax_mesh)`` (or ``num_shards=S``) runs the same
query **data-parallel** through the two-phase mergeable-state pipeline
(``partition -> local -> merge -> finalize``,
:mod:`repro.distributed.query_exec`): per-shard partial tables, one
cross-device combine tree, one finalize — bit-identical to single-device
execution for the exactly-mergeable ops.

Contracts (unchanged from the paper): non-windowed queries require the
input sorted by group id (ties contiguous; an upstream sorter provides
this); ``distinct_count`` and ``median`` additionally require keys sorted
within groups (the rank pick / dedup read runs in place) — windowed
queries sort internally, so all of these hold for free there.
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core import panestore as _panestore
from repro.core import panetable as _panetable
from repro.core import streaming as _streaming
from repro.core.swag import (_median_sorted_window, _swag, _swag_median,
                             swag_multi, swag_per_group)
from repro.core.combiners import Combiner, get_combiner
from repro.kernels import registry as _registry
from repro.obs import trace as _trace

Array = jax.Array

#: spelling conveniences accepted anywhere an op name is (the paper calls
#: distinct count "dc" throughout)
OP_ALIASES = {
    "dc": "distinct_count",
    "avg": "mean",
    "average": "mean",
    "med": "median",
}


#: rows of the pane-partial table when ``Window(capacity=...)`` is unset
TABLE_CAPACITY = 4096


def canonical_op(name: str) -> str:
    """Resolve an op-name alias (``"dc"`` -> ``"distinct_count"``, ...)."""
    return OP_ALIASES.get(name, name)


@dataclasses.dataclass(frozen=True)
class Window:
    """Sliding-window clause: aggregate the last ``ws`` tuples, advance by
    ``wa`` (time = tuple count, the paper's primary case).

    ``wa=None`` means tumbling (``wa = ws``).  ``panes`` is the tri-state
    pane-path control honoured by the reference backend (``None``
    auto-dispatches to sort-once panes when the shape allows, ``True``
    forces / ``False`` suppresses); the kernel backends encode the choice in
    the backend name (``pallas`` re-sorts, ``pallas-panes`` shares panes).

    ``wa > ws`` is allowed and means **sampling**: one window of the last
    ``ws`` tuples per ``wa``-tuple advance, so the ``wa - ws`` tuples
    between consecutive windows are never aggregated.  This is the natural
    reading of the (WS, WA) pair — each window still covers exactly the
    ``ws`` tuples before its advance boundary — and matches what the
    framing (:func:`repro.core.swag.frame_windows`) always did; it is a
    deliberate gap, not an error.

    ``ws_per_group`` selects the paper's **per-group-window approximation**
    (the last ``WS_g`` tuples *of each group*, served from the shared
    evicting pane store — :mod:`repro.core.panestore`).  It is either a
    mapping ``{group id: ws}`` (groups not listed default to ``ws``) or a
    single int (one per-group window size for every group).  ``wa`` then
    doubles as the pane width (power of two) and the evaluation stride:
    one result row set per ``wa`` stream tuples.  ``capacity`` bounds the
    shared store in pane slots (``None``: a heuristic with room for every
    listed group plus a few defaults); when live groups need more, the
    globally oldest pane is evicted and the victim group's effective
    window shrinks — the approximation the paper trades for hash-free,
    DRAM-free state.

    **Event-time clause** — ``Window(range=R, slide=S)`` (mutually
    exclusive with ``ws``/``ws_per_group``/``panes``): windows are
    *time-bounded*, covering ``[e - R, e)`` for evaluation times ``e`` at
    multiples of ``S`` (``slide=None`` means tumbling, ``S = R``;
    ``S > R`` samples, leaving time gaps — same semantics as ``wa > ws``).
    Tuples carry explicit timestamps (``execute(..., timestamps=...)``)
    and may arrive out of order within ``max_lateness`` time units of the
    maximum seen timestamp: the streaming path re-sequences them through a
    ``reorder_capacity``-slot bounded-lateness buffer and *drops* (flags,
    never silently aggregates) anything later
    (:mod:`repro.core.eventtime`).  Streaming time panes close and evict
    by **watermark advance** (``wm = max_ts - max_lateness``), not tuple
    count; ``wa`` becomes the tuple capacity of one pane slot (power of
    two, default 8) and ``capacity`` the slot count of the shared store.
    On one device, a streaming query whose every op is a partial-path op
    (:func:`repro.core.panestore.partial_path_names`) runs on the sorted
    pane-partial table of :mod:`repro.core.panetable` instead, with
    ``capacity`` its row count (default 4096) and no reorder buffer.
    Every streaming route emits **closed hopping windows**: window
    ``[e - R, e)`` once, by the first push whose watermark is at or past
    ``e``, if it holds a tuple (at most ``ceil(R / S)`` windows a push;
    later ones wait in order).
    ``strategy`` picks the batch execution strategy: ``"replay"``
    (re-aggregate each framed window — any op), ``"twostack"`` (the
    flip-batched two-stack of :mod:`repro.core.twostack` — replay-free,
    ungrouped :data:`repro.core.swag.PARTIAL_OPS` only), or ``None``
    (auto: two-stack when eligible).
    """
    ws: int | None = None
    wa: int | None = None
    panes: bool | None = None
    ws_per_group: Any = None
    capacity: int | None = None
    range: int | None = None
    slide: int | None = None
    max_lateness: int | None = None
    reorder_capacity: int | None = None
    strategy: str | None = None

    def __post_init__(self):
        if self.capacity is not None and self.capacity <= 0:
            raise ValueError(f"capacity must be positive, got {self.capacity}")
        if self.range is not None:
            if self.ws is not None or self.ws_per_group is not None:
                raise ValueError(
                    "Window(range=...) is time-bounded — the tuple-count "
                    "clauses ws / ws_per_group do not apply")
            if self.panes is not None:
                raise ValueError("panes is a count-window control; "
                                 "time-range windows pick a strategy "
                                 "(strategy='replay'|'twostack')")
            if self.range <= 0:
                raise ValueError(f"range must be positive, got {self.range}")
            slide = self.range if self.slide is None else self.slide
            if slide <= 0:
                raise ValueError(f"slide must be positive, got {slide}")
            object.__setattr__(self, "slide", slide)
            wa = 8 if self.wa is None else self.wa
            if wa <= 0 or wa & (wa - 1):
                raise ValueError(f"time-mode wa (pane-slot tuple capacity) "
                                 f"must be a positive power of two, got {wa}")
            object.__setattr__(self, "wa", wa)
            lateness = 0 if self.max_lateness is None else self.max_lateness
            if lateness < 0:
                raise ValueError(f"max_lateness must be >= 0, got {lateness}")
            object.__setattr__(self, "max_lateness", lateness)
            rc = 64 if self.reorder_capacity is None else self.reorder_capacity
            if rc <= 0 or rc & (rc - 1):
                raise ValueError(f"reorder_capacity must be a positive "
                                 f"power of two, got {rc}")
            object.__setattr__(self, "reorder_capacity", rc)
            if self.strategy not in (None, "replay", "twostack"):
                raise ValueError(f"strategy must be 'replay', 'twostack' or "
                                 f"None, got {self.strategy!r}")
            return
        for val, nm in ((self.slide, "slide"),
                        (self.max_lateness, "max_lateness"),
                        (self.reorder_capacity, "reorder_capacity"),
                        (self.strategy, "strategy")):
            if val is not None:
                raise ValueError(f"{nm} is an event-time parameter — it "
                                 f"needs Window(range=...)")
        if self.ws is None:
            raise ValueError("Window needs ws (a tuple count) or "
                             "range (a time span)")
        if self.ws <= 0:
            raise ValueError(f"ws must be positive, got {self.ws}")
        wa = self.ws if self.wa is None else self.wa
        if wa <= 0:
            raise ValueError(f"wa must be positive, got {wa}")
        object.__setattr__(self, "wa", wa)
        wpg = self.ws_per_group
        if wpg is not None and not isinstance(wpg, int):
            if isinstance(wpg, tuple):
                pairs = wpg
            else:
                try:
                    pairs = tuple(wpg.items())
                except AttributeError:
                    raise TypeError(
                        "ws_per_group must be a mapping {group id: ws}, an "
                        "int (uniform per-group window), or None; got "
                        f"{wpg!r}") from None
            wpg = tuple(sorted((int(g), int(w)) for g, w in pairs))
            object.__setattr__(self, "ws_per_group", wpg)

    @property
    def per_group(self) -> bool:
        return self.ws_per_group is not None

    @property
    def is_time(self) -> bool:
        return self.range is not None

    def store_spec(self) -> "_panestore.PaneStoreSpec":
        """The pane-store configuration this window clause implies (also
        used for streaming *global*-window queries, where ``ws`` acts as
        every group's default per-group window — the paper's streaming
        design point).  Time clauses yield a time-mode store (watermark
        retirement; panes keyed by ``ts // slide``)."""
        if self.is_time:
            from repro.core.sorter import next_pow2
            npanes = -(-self.range // self.slide) + 1
            cap = self.capacity
            if cap is None:
                cap = next_pow2(max(16, 4 * npanes))
            return _panestore.PaneStoreSpec(
                wa=self.wa, capacity=cap, default_ws=1, per_group=(),
                slide=self.slide, time_range=self.range)
        wpg = self.ws_per_group
        pairs = wpg if isinstance(wpg, tuple) else ()
        default = wpg if isinstance(wpg, int) else self.ws
        cap = self.capacity
        if cap is None:
            cap = _panestore.default_capacity(self.wa, default, pairs)
        return _panestore.PaneStoreSpec(wa=self.wa, capacity=cap,
                                        default_ws=default, per_group=pairs)

    def table_spec(self) -> "_panetable.TableSpec":
        """The pane-partial table this (time) clause implies."""
        if not self.is_time:
            raise ValueError("pane-partial tables serve Window(range=...) "
                             "only")
        return _panetable.TableSpec(
            capacity=TABLE_CAPACITY if self.capacity is None
            else self.capacity,
            time_range=self.range, slide=self.slide,
            max_lateness=self.max_lateness)

    def reorder_spec(self):
        """The bounded-lateness reorder buffer this (time) clause implies."""
        if not self.is_time:
            raise ValueError("reorder buffers serve Window(range=...) only")
        from repro.core import eventtime as _eventtime
        return _eventtime.ReorderSpec(capacity=self.reorder_capacity,
                                      max_lateness=self.max_lateness)


def _twostack_reason(query: "Query") -> str | None:
    """Why the two-stack strategy cannot serve ``query`` (None = it can)."""
    from repro.core.swag import PARTIAL_OPS
    if query.group_by:
        return ("the flip-batched two-stack aggregates the whole stream "
                "(group_by=False); grouped time windows take the replay "
                "strategy")
    bad = sorted(set(query.op_names) - set(PARTIAL_OPS))
    if bad:
        return (f"two-stack scans need single-array monoid states "
                f"({sorted(PARTIAL_OPS)}); {bad} take the replay strategy")
    return None


def resolve_time_strategy(query: "Query") -> str:
    """Resolve a time-window query's execution strategy (validating an
    explicit ``Window(strategy=...)`` — never a silent fallback)."""
    w = query.window
    if w.strategy == "twostack":
        reason = _twostack_reason(query)
        if reason is not None:
            raise ValueError(f"Window(strategy='twostack') cannot run this "
                             f"query: {reason}")
        return "twostack"
    if w.strategy == "replay":
        return "replay"
    return "twostack" if _twostack_reason(query) is None else "replay"


@dataclasses.dataclass(frozen=True)
class Query:
    """Declarative aggregation query — the ``function_select`` spec.

    Fields:
      ops: one combiner name / :class:`Combiner`, or a tuple of them; the
        non-incremental ``"median"`` is a valid op (non-windowed queries
        additionally need keys sorted within groups, like ``"dc"``).
        Aliases from :data:`OP_ALIASES` are normalised (``"dc"`` ->
        ``"distinct_count"``).
      group_by: when False the whole stream is one group (``groups`` may be
        omitted at execute time) — ``SELECT f(k) FROM t`` without the
        ``GROUP BY``.
      window: optional :class:`Window` clause (SWAG).
      interpolate: median only — return the float midpoint of the two
        middle elements instead of the lower median.
      n_valid: optional static prefix length — only the first ``n_valid``
        tuples are real (padding at the tail).  An array can also be passed
        to :func:`execute` for traced prefixes.
      streaming: thread a rolling carry across :func:`execute` calls
        (multi-batch mode; the paper's non-blocking pipeline).
      presorted: windowed queries only — promise each framed window is
        already (group, key)-sorted, skipping the per-window sorter.
    """
    ops: Any
    group_by: bool = True
    window: Window | None = None
    interpolate: bool = False
    n_valid: int | None = None
    streaming: bool = False
    presorted: bool = False

    def __post_init__(self):
        ops = self.ops
        if isinstance(ops, (str, Combiner)):
            ops = (ops,)
        ops = tuple(canonical_op(op) if isinstance(op, str) else op
                    for op in ops)
        if not ops:
            raise ValueError("Query needs at least one op")
        names = [op.name if isinstance(op, Combiner) else op for op in ops]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate ops in query: {names}")
        object.__setattr__(self, "ops", ops)

    @property
    def op_names(self) -> tuple[str, ...]:
        return tuple(op.name if isinstance(op, Combiner) else op
                     for op in self.ops)


class AggResult(NamedTuple):
    """The single result type every backend returns.

    ``values`` maps op name -> value column; all columns share ``groups`` /
    ``valid`` / ``num_groups``.  Windowed queries carry a leading
    ``[num_windows]`` axis on every array; streaming queries return the
    batch layout of the paper's non-blocking pipeline (``N + 1`` slots, the
    +1 holding a group closed exactly at the batch boundary).
    """
    groups: Array           # [N] int32 — compacted group ids (padded tail)
    values: dict            # {op name: [N] aggregate column}
    valid: Array            # [N] bool — which slots hold a real group
    num_groups: Array       # scalar int32 (per window when windowed)
    #: engine telemetry (``execute(..., collect_stats=True)``): a dict of
    #: :mod:`repro.obs.counters` values — None when stats are off (the
    #: default), so the result pytree is unchanged for existing callers
    stats: Any = None
    #: event-time streaming only: ``[rows]`` window end of each row (rows
    #: are closed hopping windows; TS_MIN where a row holds none)
    window_ends: Any = None


@dataclasses.dataclass(frozen=True)
class Plan:
    """A Query lowered onto a concrete backend and stage pipeline.

    Hashable and reusable: build once (validating spec + backend capability
    up front), execute many times — :func:`execute` accepts either a
    ``Query`` (planned on the fly) or a prebuilt ``Plan``.

    ``stages`` is the explicit execution pipeline.  Single-shard plans run
    ``("local", "finalize")``; sharded plans (``num_shards > 1``, or a
    ``mesh=`` handed to :func:`execute`) run the two-phase mergeable-state
    pipeline ``("partition", "local", "merge", "finalize")`` of
    :mod:`repro.distributed.query_exec` — per-shard partial tables, one
    cross-device combine tree, one finalize.
    """
    query: Query
    backend: str            # concrete registry name (never "auto")
    path: str               # "engine" | "window" | "stream"
    note: str = ""
    num_shards: int = 1
    stages: tuple = ("local", "finalize")


def _validate_sharded(query: Query, backend: str, num_shards: int) -> None:
    """Reject queries whose states cannot merge across shards — at plan
    time, with the reason (never a silent wrong answer)."""
    w = query.window
    if w is not None and w.per_group:
        raise ValueError(
            "per-group windows (Window(ws_per_group=...)) replay one shared "
            "evicting pane store — a sequential structure with no "
            "cross-shard merge; run them single-device")
    if w is not None and query.streaming and not w.is_time:
        raise ValueError(
            "streaming windowed queries thread one shared pane store as "
            "their carry and cannot shard; stream the non-windowed query "
            "per shard instead")
    if w is not None and w.is_time and not query.streaming:
        raise ValueError(
            "batch time-range windows frame by concrete host-side "
            "timestamps and run single-device; shard the streaming path "
            "(Query(streaming=True)) instead — per-shard reorder buffers "
            "release against the min-merged watermark")
    if query.presorted:
        raise ValueError("presorted conflicts with sharded execution — the "
                         "local phase sorts per shard/pane")
    if w is not None and w.is_time:
        # sharded event-time streaming merges *emissions* (per-shard
        # reorder buffers feed one shared time-pane store), so any replay
        # op works — the mergeable-combiner constraint does not apply
        return
    for op, nm in zip(query.ops, query.op_names):
        if nm == "median":
            if query.streaming:
                raise ValueError("streaming median has no mergeable carry")
            continue
        comb = op if isinstance(op, Combiner) else get_combiner(nm)
        if not comb.mergeable:
            raise ValueError(
                f"op {nm!r} has no cross-shard partial-state merge (its "
                f"lifted positions are shard-local); run it single-device")
    if backend == "pallas" and query.window is None:
        from repro.distributed.query_exec import KERNEL_STATE_OPS
        # median rides the sorted-run channel, never the group-by kernel
        bad = sorted(set(query.op_names) - set(KERNEL_STATE_OPS)
                     - {"median"})
        if bad:
            raise ValueError(
                f"the pallas group-by kernel emits finalized values; only "
                f"{sorted(KERNEL_STATE_OPS)} coincide with their partial "
                f"states, so {bad} cannot shard on this backend — use "
                f"reference")


def plan(query: Query, *, backend: str | None = None, num_shards: int = 1,
         devices=None) -> Plan:
    """Validate ``query``, choose a backend, and lay out the stage pipeline.

    Precedence: ``backend`` argument > ``REPRO_BACKEND`` env var > ``auto``
    (capability probe: reference on CPU, fused kernels on accelerators).
    Raises ``ValueError`` when an explicitly requested backend cannot run
    the query (never a silent fallback).

    ``num_shards > 1`` plans the two-phase mergeable-state pipeline
    (``partition -> local -> merge -> finalize``); ``devices`` (e.g. a
    mesh's devices) makes the ``auto`` probe answer for the hardware the
    shards actually run on.

    Streaming windowed queries run on the per-group pane store: with a
    plain ``Window(ws)`` the window counts each group's *own* last ``ws``
    tuples (the paper's approximation — different numbers than the same
    window executed batch-at-a-time, which frames the raw stream); the
    plan's ``note`` records the reinterpretation.
    """
    if not isinstance(query, Query):
        raise TypeError(f"expected a Query, got {type(query).__name__}")
    if query.window is not None and query.window.is_time:
        if query.presorted:
            raise ValueError("presorted does not apply to time-range "
                             "windows — they frame by timestamp")
        resolve_time_strategy(query)  # explicit strategy validated now
        query.window.store_spec()     # wa/capacity validated now
    elif query.window is not None and (query.window.per_group
                                       or query.streaming):
        # both the per-group batch path and every streaming windowed query
        # run on the shared pane store (streaming global windows are the
        # paper's approximation: ws becomes each group's default window)
        if query.presorted:
            raise ValueError("presorted is meaningless with the pane "
                             "store — it frames and sorts panes itself")
        if query.window.panes is False:
            raise ValueError("Window(panes=False) conflicts with "
                             "ws_per_group / streaming windows: the pane "
                             "store *is* the pane path")
        query.window.store_spec()  # validate wa/capacity/ws_per_group now
    names = query.op_names
    if query.interpolate and "median" not in names:
        raise ValueError("interpolate=True applies to the median op only")
    if query.n_valid is not None and query.window is not None \
            and not (query.streaming and query.window.is_time):
        # exception: event-time streaming pushes — the reorder buffer
        # ingests a masked prefix per push
        raise ValueError("n_valid applies to non-windowed queries (windows "
                         "frame a dense stream)")
    for op in query.ops:
        if isinstance(op, str) and op != "median":
            get_combiner(op)  # raises on unknown names

    name = _registry.resolve_backend(backend)
    note = ""
    if name == "auto":
        name, how = _registry.choose_backend(query, devices,
                                             num_shards=num_shards)
        note = f"auto ({how})"
    reason = _registry.get_backend(name).supports(query)
    if reason is not None:
        raise _registry.unsupported_error(name, reason)

    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    stages = ("local", "finalize")
    if num_shards > 1:
        try:
            _validate_sharded(query, name, num_shards)
        except ValueError:
            # an *auto*-chosen kernel backend must not turn a shardable
            # query into a plan failure — fall back to the total reference
            # backend (an explicitly requested backend still raises)
            if not note.startswith("auto") or name == "reference":
                raise
            name = "reference"
            _validate_sharded(query, name, num_shards)
            note += "; kernel backend cannot shard this query"
        stages = ("partition", "local", "merge", "finalize")

    path = ("stream" if query.streaming
            else "window" if query.window is not None
            else "engine")
    if path == "stream" and query.window is not None \
            and query.window.is_time:
        if _table_ops(query, num_shards, jnp.int32):
            route = "event-time: pane-partial table, closed hopping windows"
            if not _table_ops(query, num_shards, jnp.float32):
                route += " (float keys with sum/mean: reorder buffer)"
        else:
            route = ("event-time: reorder buffer and time-pane store, "
                     "closed hopping windows")
        note = (note + "; " if note else "") + route
    elif path == "stream" and query.window is not None \
            and not query.window.per_group:
        # NOT the batch semantics: a streamed global window runs on the
        # pane store, where ws becomes each group's default per-group
        # window (the paper's approximation) — flag it on the plan
        note = (note + "; " if note else "") + \
            "stream-window: ws serves as each group's per-group window"
    return Plan(query=query, backend=name, path=path, note=note,
                num_shards=num_shards, stages=stages)


def _table_ops(query: Query, num_shards: int, key_dtype) -> bool:
    return (num_shards == 1 and all(_panestore.partial_path_names(
        query.op_names, key_dtype)))


def table_route(p: Plan, key_dtype=jnp.int32) -> bool:
    """Whether the streaming event-time plan ``p`` runs, for keys of
    ``key_dtype``, on the sorted pane-partial table
    (:mod:`repro.core.panetable`): one device, and every op on the
    partial path (float sum/mean keep the reorder buffer, whose replay
    sums in a fixed order)."""
    w = p.query.window
    return (p.path == "stream" and w is not None and w.is_time
            and _table_ops(p.query, p.num_shards, key_dtype))


def _combiners(query: Query) -> tuple[Combiner | None, ...]:
    """Resolved combiners aligned with ``query.ops`` (None marks median)."""
    return tuple(None if (isinstance(op, str) and op == "median")
                 else (op if isinstance(op, Combiner) else get_combiner(op))
                 for op in query.ops)


def _prepare_inputs(query: Query, groups, keys, n_valid):
    if keys is None:
        raise ValueError("keys are required")
    keys = jnp.asarray(keys)
    if query.group_by:
        if groups is None:
            raise ValueError("Query(group_by=True) needs a groups column")
        groups = jnp.asarray(groups)
    else:
        # the whole stream is one group — SELECT f(k) FROM t
        groups = jnp.zeros(keys.shape[-1:], jnp.int32)
    if n_valid is None:
        n_valid = query.n_valid
    return groups, keys, n_valid


def stream_fn(p: Plan, *, p_ports: int = 4, mesh=None,
              collect_stats: bool = False):
    """Return the raw streaming step of a planned streaming query:
    ``(groups, keys, state, n_valid) -> ((groups, values, valid, num, rr),
    state)`` — jit-friendly (close over the static plan).

    Non-windowed streams thread per-op :class:`segscan.Carry` tuples;
    windowed streams thread a :class:`repro.core.panestore.PaneStoreState`
    (push the batch, then emit one per-group evaluation).  Sharded plans
    (``num_shards > 1``) accept the same whole batch, run per-shard partial
    tables through the combine tree (over ``mesh`` when given), and fold
    the carry at emit time — bit-identical slots.

    ``collect_stats=True`` expects (and returns) the wrapped state
    ``(engine state, counters dict)`` of
    ``init_stream_state(..., collect_stats=True)`` — the counters
    accumulate across pushes (:mod:`repro.obs.counters`); the default
    traces exactly the pre-observability jaxpr."""
    if p.path != "stream":
        raise ValueError("stream_fn needs a streaming plan")
    q = p.query
    if collect_stats:
        from repro.obs import counters as _c

    if q.window is not None and q.window.is_time:
        from repro.core import eventtime as _eventtime
        spec = q.window.store_spec()
        rspec = q.window.reorder_spec()
        tspec = q.window.table_spec()
        time_range = q.window.range
        lateness = q.window.max_lateness

        if p.num_shards > 1:
            from repro.distributed import query_exec as _qx

            def sharded_time_step(groups, keys, state, n_valid=None,
                                  timestamps=None):
                if timestamps is None:
                    raise ValueError("event-time streaming pushes need "
                                     "timestamps=")
                if not collect_stats:
                    return _qx.stream_push_eventtime_sharded(
                        q, groups, keys, timestamps, state,
                        num_shards=p.num_shards, mesh=mesh, n_valid=n_valid,
                        p_ports=p_ports)
                inner, counters = state
                ports, inner, counters = _qx.stream_push_eventtime_sharded(
                    q, groups, keys, timestamps, inner,
                    num_shards=p.num_shards, mesh=mesh, n_valid=n_valid,
                    p_ports=p_ports, counters=counters)
                return ports, (inner, counters)

            return sharded_time_step

        def time_step(groups, keys, state, n_valid=None, timestamps=None):
            if timestamps is None:
                raise ValueError("event-time streaming pushes need "
                                 "timestamps=")
            counters = None
            if collect_stats:
                state, counters = state
            if isinstance(state, _panetable.TableState) and counters is None:
                out, state = _panetable.push(tspec, state, groups, keys,
                                             timestamps, q.ops,
                                             n_valid=n_valid)
            elif isinstance(state, _panetable.TableState):
                out, state, counters = _panetable.push(
                    tspec, state, groups, keys, timestamps, q.ops,
                    n_valid=n_valid, counters=counters)
            else:
                rstate, pstate, next_end = state
                if counters is None:
                    emit, rstate = _eventtime.reorder_push(
                        rspec, rstate, timestamps, groups, keys,
                        n_valid=n_valid)
                else:
                    emit, rstate, counters = _eventtime.reorder_push(
                        rspec, rstate, timestamps, groups, keys,
                        n_valid=n_valid, counters=counters)
                wm = rstate.max_ts - lateness
                if counters is None:
                    pstate = _panestore.push_time(
                        spec, pstate, emit.groups, emit.keys, emit.ts,
                        live=emit.live, retire_below=next_end - time_range)
                else:
                    pstate, counters = _panestore.push_time(
                        spec, pstate, emit.groups, emit.keys, emit.ts,
                        live=emit.live, retire_below=next_end - time_range,
                        counters=counters)
                out, next_end = _panestore.emit_time_windows(
                    spec, pstate, q.ops, next_end, wm, tspec.rows,
                    interpolate=q.interpolate)
                out = _panetable.Emission(*out)
                state = (rstate, pstate, next_end)
                if counters is not None:
                    counters = _c.put(counters, "late_dropped",
                                      rstate.dropped)
                    counters = _c.put(counters, "watermark", wm)
                    counters = _c.bump(counters, "windows_emitted", jnp.sum(
                        (out.num_groups > 0).astype(jnp.int32)))
            ports = time_ports(out, p_ports)
            if counters is None:
                return ports, state
            return ports, (state, counters)

        return time_step

    if p.num_shards > 1:
        from repro.distributed import query_exec as _qx
        combiners = _combiners(q)

        def sharded_step(groups, keys, carries, n_valid=None):
            if not collect_stats:
                return _qx.stream_push_sharded(
                    q, groups, keys, carries, combiners,
                    num_shards=p.num_shards, mesh=mesh, n_valid=n_valid,
                    p_ports=p_ports)
            inner, counters = carries
            ports, inner, counters = _qx.stream_push_sharded(
                q, groups, keys, inner, combiners,
                num_shards=p.num_shards, mesh=mesh, n_valid=n_valid,
                p_ports=p_ports, counters=counters)
            return ports, (inner, counters)

        return sharded_step

    if q.window is not None:
        spec = q.window.store_spec()

        def store_step(groups, keys, state, n_valid=None):
            counters = None
            if collect_stats:
                state, counters = state
            if counters is None:
                state = _panestore.push(spec, state, groups, keys,
                                        n_valid=n_valid)
            else:
                state, counters = _panestore.push(spec, state, groups, keys,
                                                  n_valid=n_valid,
                                                  counters=counters)
                # which ops each push's evaluation dispatches on the
                # per-pane partial fast path vs merge-replay (static per
                # plan — gauge, not accumulator)
                names = [op.name if isinstance(op, Combiner) else op
                         for op in q.ops]
                psel = ([False] * len(names) if spec.is_time else
                        _panestore.partial_path_names(names,
                                                      state.keys.dtype))
                counters = _c.put(counters, "pergroup_partial_ops",
                                  jnp.asarray(sum(psel), jnp.int32))
                counters = _c.put(counters, "pergroup_merge_ops",
                                  jnp.asarray(len(psel) - sum(psel),
                                              jnp.int32))
            g, values, valid, num = _panestore.replay(
                spec, state, q.ops, interpolate=q.interpolate)
            rr = jnp.where(valid, jnp.arange(spec.capacity) % p_ports, -1)
            if counters is None:
                return (g, values, valid, num, rr), state
            return (g, values, valid, num, rr), (state, counters)

        return store_step

    combiners = _combiners(q)

    def step(groups, keys, carries, n_valid=None):
        if not collect_stats:
            return _streaming.stream_push(groups, keys, carries, combiners,
                                          n_valid=n_valid, p_ports=p_ports)
        inner, counters = carries
        out, inner = _streaming.stream_push(groups, keys, inner, combiners,
                                            n_valid=n_valid, p_ports=p_ports)
        n = groups.shape[-1]
        pushed = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
        counters = _c.bump(counters, "stream_tuples", pushed)
        counters = _c.bump(counters, "stream_emitted", out[3])
        return out, (inner, counters)

    return step


def time_ports(out, p_ports: int = 4) -> tuple:
    """An event-time :class:`repro.core.panetable.Emission` as the streaming
    port tuple ``(groups, values, valid, num, rr, ends)``: ``[rows,
    lanes]`` arrays, one row per emitted window."""
    lanes = out.groups.shape[-1]
    rr = jnp.where(out.valid, jnp.arange(lanes) % p_ports, -1)
    return (out.groups, out.values, out.valid, out.num_groups, rr, out.ends)


def _init_stream_counters(p: Plan, key_dtype=jnp.int32) -> dict:
    """The zeroed counters dict a stats-collecting stream carry starts
    from — keyed up front (every key the step will touch) so the carry
    pytree structure is stable from the first push on (one jit trace)."""
    from repro.core.eventtime import TS_MIN
    from repro.obs import counters as _c
    w = p.query.window
    if table_route(p, key_dtype):
        return _c.init(late_dropped=jnp.zeros((), jnp.int32),
                       pane_evictions=jnp.zeros((), jnp.int32),
                       watermark=jnp.asarray(TS_MIN, jnp.int32),
                       windows_emitted=jnp.zeros((), jnp.int32),
                       pane_rows_hwm=jnp.zeros((), jnp.int32),
                       table_merge_wide=jnp.zeros((), jnp.int32))
    if w is not None and w.is_time:
        c = _c.init(reorder_depth_hwm=jnp.zeros((), jnp.int32),
                    reorder_forced_pops=jnp.zeros((), jnp.int32),
                    pane_evictions=jnp.zeros((), jnp.int32),
                    pane_occupancy_hwm=jnp.zeros((), jnp.int32),
                    late_dropped=jnp.zeros((), jnp.int32),
                    watermark=jnp.asarray(TS_MIN, jnp.int32),
                    windows_emitted=jnp.zeros((), jnp.int32))
        if p.num_shards > 1:
            c["watermark_lag"] = jnp.zeros((), jnp.int32)
        return c
    if p.num_shards > 1:
        # the combine-tree telemetry is static per plan; seed with the
        # correct round count so the carry structure never changes
        rounds = (p.num_shards - 1).bit_length()  # log2 of next pow2
        return _c.init(stream_tuples=jnp.zeros((), jnp.int32),
                       combine_rounds=jnp.asarray(rounds, jnp.int32),
                       combine_round_width=jnp.zeros((rounds,), jnp.int32),
                       combine_round_groups=jnp.zeros((rounds,), jnp.int32),
                       combine_round_bytes=jnp.zeros((rounds,), jnp.float32))
    if w is not None:
        return _c.init(pane_evictions=jnp.zeros((), jnp.int32),
                       pane_occupancy_hwm=jnp.zeros((), jnp.int32),
                       pane_dir_fallbacks=jnp.zeros((), jnp.int32),
                       pergroup_partial_ops=jnp.zeros((), jnp.int32),
                       pergroup_merge_ops=jnp.zeros((), jnp.int32))
    return _c.init(stream_tuples=jnp.zeros((), jnp.int32),
                   stream_emitted=jnp.zeros((), jnp.int32))


def init_stream_state(p: Plan, key_dtype=jnp.int32,
                      collect_stats: bool = False):
    """Fresh state for a streaming plan: per-op carries, a pane store when
    the query is windowed, or ``(reorder buffer(s), time-pane store)`` for
    event-time windows (sharded event-time plans stack one reorder buffer
    per shard — each shard tracks its own watermark).

    ``collect_stats=True`` wraps the state as ``(state, counters)`` — the
    shape ``stream_fn(..., collect_stats=True)`` threads; pass the same
    flag to both (``execute`` does)."""
    from repro.core import segscan
    if table_route(p, key_dtype):
        state = _panetable.init_table(p.query.window.table_spec(),
                                      p.query.ops, key_dtype)
    elif p.query.window is not None and p.query.window.is_time:
        from repro.core import eventtime as _eventtime
        rstate = _eventtime.init_reorder(p.query.window.reorder_spec(),
                                         key_dtype)
        if p.num_shards > 1:
            rstate = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (p.num_shards,) + x.shape),
                rstate)
        state = (rstate,
                 _panestore.init_store(p.query.window.store_spec(),
                                       key_dtype),
                 jnp.asarray(_eventtime.TS_MIN, jnp.int32))
    elif p.query.window is not None:
        state = _panestore.init_store(p.query.window.store_spec(), key_dtype)
    else:
        state = tuple(segscan.init_carry(c, key_dtype)
                      for c in _combiners(p.query))
    if collect_stats:
        return (state, _init_stream_counters(p, key_dtype))
    return state


def _execute_engine(p: Plan, groups, keys, n_valid, *, tile, interpret):
    q = p.query
    names = q.op_names
    if p.backend == "pallas":
        if "median" in names:
            # median needs whole groups in one tile: run the fused one-frame
            # swag kernel over the pow2-padded stream (all ops ride along)
            from repro.kernels.swag.ops import _engine_median_kernel_exec
            og, ovs, valid, num = _engine_median_kernel_exec(
                groups, keys, names, n_valid=n_valid, interpret=interpret)
            return AggResult(og, ovs, valid, num)
        from repro.kernels.groupagg.ops import _groupagg_kernel_exec
        values = {}
        shared = None
        # the tiled groupagg kernel is single-op (per-tile carry stitching);
        # multi-op fusion is the reference path's job — see swag for the
        # windowed fused kernels
        for op, name in zip(q.ops, names):
            r = _groupagg_kernel_exec(groups, keys, op, n_valid=n_valid,
                                      tile=tile, interpret=interpret)
            values[name] = r.values
            shared = shared or (r.groups, r.valid, r.num_groups)
        return AggResult(shared[0], values, shared[1], shared[2])

    non_median = tuple(op for op, nm in zip(q.ops, names) if nm != "median")
    values = {}
    shared = None
    if non_median:
        (g, vals, valid, num), _ = _engine.multi_engine_step(
            groups, keys, non_median, n_valid=n_valid)
        values.update(vals)
        shared = (g, valid, num)
    if "median" in names:
        # grouped median without a window: the engine pass provides segment
        # offsets + cardinalities over the (group, key)-sorted stream, and
        # the rank pick reads the middle element(s) in place (same
        # sorted-within-groups contract as distinct_count)
        t = _median_sorted_window(groups, keys, interpolate=q.interpolate,
                                  n_valid=n_valid)
        values["median"] = t.medians
        shared = shared or (t.groups, t.valid, t.num_groups)
    return AggResult(shared[0], values, shared[1], shared[2])


def _execute_window(p: Plan, groups, keys, *, use_xla_sort, interpret,
                    counters=None):
    q = p.query
    w = q.window
    if w.per_group:
        spec = w.store_spec()
        if p.backend == "pallas-panestore":
            from repro.kernels.swag.ops import _swag_pergroup_kernel_exec
            return AggResult(*_swag_pergroup_kernel_exec(
                groups, keys, spec=spec, ops=q.op_names,
                interpret=interpret, counters=counters))
        if counters is not None:
            (og, values, valid, num), _, counters = swag_per_group(
                groups, keys, spec=spec, ops=q.ops,
                interpolate=q.interpolate, counters=counters)
            return AggResult(og, values, valid, num, counters)
        (og, values, valid, num), _ = swag_per_group(
            groups, keys, spec=spec, ops=q.ops, interpolate=q.interpolate)
        return AggResult(og, values, valid, num)

    if p.backend in ("pallas", "pallas-panes"):
        from repro.kernels.swag.ops import _swag_kernel_exec
        panes = True if p.backend == "pallas-panes" else False
        og, ovs, valid, oc = _swag_kernel_exec(
            groups, keys, ws=w.ws, wa=w.wa, ops=q.op_names,
            interpret=interpret, panes=panes)
        return AggResult(og, ovs, valid, oc)

    if len(q.ops) > 1:
        g, values, valid, num = swag_multi(
            groups, keys, ws=w.ws, wa=w.wa, ops=q.ops,
            interpolate=q.interpolate, presorted=q.presorted,
            use_xla_sort=use_xla_sort, panes=w.panes)
        return AggResult(g, values, valid, num)

    (op,) = q.ops
    name, = q.op_names
    if name == "median":
        r = _swag_median(groups, keys, ws=w.ws, wa=w.wa,
                         interpolate=q.interpolate,
                         use_xla_sort=use_xla_sort, panes=w.panes)
        return AggResult(r.groups, {name: r.medians}, r.valid, r.num_groups)
    r = _swag(groups, keys, ws=w.ws, wa=w.wa, op=op,
              presorted=q.presorted, use_xla_sort=use_xla_sort,
              panes=w.panes)
    return AggResult(r.groups, {name: r.values}, r.valid, r.num_groups)


def _execute_time_window(p: Plan, groups, keys, timestamps, *,
                         interpret):
    """Batch execution of ``Window(range=..., slide=...)``: sort by
    timestamp once (host-side layout — window count/width are shapes),
    then either **replay** each framed window (any op; reference engine
    rows or the fused Pallas sort+tails kernel) or run the flip-batched
    **two-stack** (ungrouped PARTIAL_OPS; jnp scans or the Pallas
    stack-flip kernel)."""
    from repro.core import eventtime as _eventtime
    from repro.kernels import common as _common
    q = p.query
    w = q.window
    ts = _eventtime.concrete_timestamps(timestamps)
    if ts.shape[0] != keys.shape[-1]:
        raise ValueError(f"timestamps length {ts.shape[0]} != stream "
                         f"length {keys.shape[-1]}")
    layout = _eventtime.time_window_layout(ts, w.range, w.slide)
    order = jnp.asarray(layout.order, jnp.int32)
    gs = jnp.take(groups.astype(jnp.int32), order)
    ks = jnp.take(keys, order)
    strategy = resolve_time_strategy(q)
    kernels = p.backend != "reference"
    interp = _common.default_interpret(interpret) if kernels else False

    if strategy == "twostack":
        from repro.core import twostack as _twostack
        epochs = _twostack.epoch_layout(layout.starts, layout.ends)
        values, cnt = _twostack.twostack_time_windows(
            ks, layout, epochs, q.op_names,
            use_kernel=kernels, interpret=interp)
        valid = (cnt > 0)[:, None]
        og = jnp.where(valid, 0, _engine.PAD_GROUP)
        values = {name: v[:, None] for name, v in values.items()}
        return AggResult(og, values, valid, valid[:, 0].astype(jnp.int32))

    fg, fk, cnt = _eventtime.frame_time_windows(layout, gs, ks,
                                                _engine.PAD_GROUP)
    if kernels:
        from repro.kernels.swag.ops import _timeframe_kernel_exec
        og, ovs, valid, num = _timeframe_kernel_exec(
            fg, fk, ops=q.op_names, interpret=interpret)
        return AggResult(og, ovs, valid, num)

    names = q.op_names
    non_median = tuple(op for op, nm in zip(q.ops, names) if nm != "median")

    def row(g, k, c):
        # PAD_GROUP sorts last, so the live lanes form the sorted prefix
        # n_valid needs (the engine masks the PAD tail through it)
        g2, k2 = jax.lax.sort((g, k), num_keys=2)
        values = {}
        shared = None
        if non_median:
            (og, vals, valid, num), _ = _engine.multi_engine_step(
                g2, k2, non_median, n_valid=c)
            values.update(vals)
            shared = (og, valid, num)
        if "median" in names:
            t = _median_sorted_window(g2, k2, interpolate=q.interpolate,
                                      n_valid=c)
            values["median"] = t.medians
            shared = shared or (t.groups, t.valid, t.num_groups)
        return shared[0], values, shared[1], shared[2]

    if layout.starts.shape[0] == 0:
        wcap = layout.wcap
        res = jax.eval_shape(row, jax.ShapeDtypeStruct((wcap,), jnp.int32),
                             jax.ShapeDtypeStruct((wcap,), keys.dtype),
                             jax.ShapeDtypeStruct((), jnp.int32))
        zeros = jax.tree.map(
            lambda s: jnp.zeros((0,) + s.shape, s.dtype), res)
        return AggResult(*zeros)
    og, values, valid, num = jax.vmap(row)(fg, fk, cnt)
    return AggResult(og, values, valid, num)


def _execute_sharded(p: Plan, groups, keys, n_valid, *, mesh, use_xla_sort,
                     interpret, tile, counters=None):
    from repro.distributed import query_exec as _qx
    q = p.query
    if p.path == "window":
        if n_valid is not None:
            raise ValueError("n_valid applies to non-windowed queries")
        # the per-window combine trees run vmapped (one tiny tree per
        # window) — no shard-tree telemetry to record there
        g, values, valid, num = _qx._window_sharded(
            q, groups, keys, num_shards=p.num_shards, mesh=mesh,
            backend=p.backend, use_xla_sort=use_xla_sort,
            interpret=interpret)
    elif counters is not None:
        g, values, valid, num, counters = _qx._engine_sharded(
            q, groups, keys, n_valid, num_shards=p.num_shards, mesh=mesh,
            backend=p.backend, tile=tile, interpret=interpret,
            counters=counters)
    else:
        g, values, valid, num = _qx._engine_sharded(
            q, groups, keys, n_valid, num_shards=p.num_shards, mesh=mesh,
            backend=p.backend, tile=tile, interpret=interpret)
    return AggResult(g, values, valid, num, counters)


def execute(plan_or_query, groups, keys=None, *, state=None, backend=None,
            n_valid=None, timestamps=None, mesh=None,
            num_shards: int | None = None,
            use_xla_sort: bool = False, interpret: bool | None = None,
            tile: int = 1024, collect_stats: bool = False):
    """Run a :class:`Query` (planned on the fly) or a prebuilt :class:`Plan`.

    Args:
      plan_or_query: the spec; a ``Plan`` skips re-planning (hot loops).
      groups: [N] group-id column (may be ``None`` for
        ``Query(group_by=False)``).
      keys:   [N] value column.
      state: streaming queries only — carries from the previous call
        (``None`` starts a fresh stream).
      timestamps: [N] event-time column — required by (and only accepted
        with) ``Window(range=...)`` queries.  Batch execution frames
        windows from the *concrete* values (call outside jit); streaming
        pushes accept tracers (the watermark lives in the carry).
      backend: override the plan's backend (re-plans when it differs).
      n_valid: traced prefix-length override of ``query.n_valid``.
      mesh: a :class:`jax.sharding.Mesh` — run the two-phase
        mergeable-state pipeline data-parallel over the mesh's devices
        (its flattened axes are the shard axis); the local phase runs
        under ``shard_map`` and only compact partial tables / sorted runs
        cross devices.  Bit-identical to single-device execution for the
        exactly-mergeable ops (sum/count/min/max/mean/dc/median on
        integer keys).
      num_shards: shard count without a mesh — the identical two-phase
        pipeline on one device (``vmap`` locals); useful for testing the
        merge algebra anywhere.  With ``mesh`` it must match the device
        count (or be omitted).
      use_xla_sort: reference backend — use ``lax.sort`` instead of the
        bitonic network for per-window sorting.
      interpret: kernel backends — force/suppress Pallas interpret mode
        (``None``: the capability probe picks interpret on CPU).
      tile: pallas group-by backend — kernel tile length.
      collect_stats: thread jit-safe engine counters
        (:mod:`repro.obs.counters`) through execution and surface them as
        ``AggResult.stats``; each concrete (non-traced) call also records
        observed tuples/s in :data:`repro.obs.registry.METRICS` under
        ``(backend, plan fingerprint)``.  The default (``False``) traces
        the identical jaxpr as before the counters existed.  Streaming
        queries must keep the flag constant across a stream (the counters
        live in the carry): pass ``state=None`` to toggle it.

    Returns:
      ``(AggResult, new_state)``; ``new_state`` is ``None`` unless the query
      streams.
    """
    t0 = _time.perf_counter()
    devices = None
    if mesh is not None:
        from repro.distributed import query_exec as _qx
        mesh_shards = _qx.mesh_num_shards(mesh)
        if num_shards is not None and num_shards != mesh_shards:
            raise ValueError(
                f"num_shards={num_shards} contradicts the mesh's "
                f"{mesh_shards} devices; pass one or the other")
        num_shards = mesh_shards
        devices = list(mesh.devices.flat)

    with _trace.span("plan"):
        if isinstance(plan_or_query, Plan):
            p = plan_or_query
            want_backend = backend if backend is not None else p.backend
            want_shards = (num_shards if num_shards is not None
                           else p.num_shards)
            if want_backend != p.backend or want_shards != p.num_shards:
                p = plan(p.query, backend=want_backend,
                         num_shards=want_shards, devices=devices)
        else:
            p = plan(plan_or_query, backend=backend,
                     num_shards=num_shards if num_shards is not None else 1,
                     devices=devices)

    groups, keys, n_valid = _prepare_inputs(p.query, groups, keys, n_valid)
    n = groups.shape[-1]

    is_time = p.query.window is not None and p.query.window.is_time
    if is_time and timestamps is None:
        raise ValueError("Window(range=...) queries aggregate by event "
                         "time; pass timestamps=")
    if not is_time and timestamps is not None:
        raise ValueError("timestamps apply to time-range windows "
                         "(Window(range=...)) only")

    if p.path == "stream":
        if state is None:
            state = init_stream_state(p, keys.dtype,
                                      collect_stats=collect_stats)
        elif collect_stats != _state_collects_stats(state):
            raise ValueError(
                "collect_stats must stay constant across a stream — the "
                "counters live in the threaded carry; pass state=None to "
                "start a new stream with the other setting")
        step = stream_fn(p, mesh=mesh, collect_stats=collect_stats)
        with _trace.span("dispatch", backend=p.backend, path="stream"):
            ends = None
            if is_time:
                (g, values, valid, num, _rr, ends), new_state = step(
                    groups, keys, state, n_valid, timestamps)
            else:
                (g, values, valid, num, _rr), new_state = step(
                    groups, keys, state, n_valid)
        stats = dict(new_state[1]) if collect_stats else None
        res = AggResult(g, values, valid, num, stats, ends)
        if collect_stats:
            _observe_throughput(p, res, n, t0)
        return res, new_state

    counters = None
    if collect_stats:
        counters = {}

    if p.num_shards > 1:
        with _trace.span("dispatch", backend=p.backend, path=p.path,
                         shards=p.num_shards):
            res = _execute_sharded(p, groups, keys, n_valid, mesh=mesh,
                                   use_xla_sort=use_xla_sort,
                                   interpret=interpret, tile=tile,
                                   counters=counters)
    elif p.path == "window":
        if n_valid is not None:
            raise ValueError("n_valid applies to non-windowed queries")
        with _trace.span("dispatch", backend=p.backend, path=p.path):
            if is_time:
                res = _execute_time_window(p, groups, keys, timestamps,
                                           interpret=interpret)
            else:
                res = _execute_window(p, groups, keys,
                                      use_xla_sort=use_xla_sort,
                                      interpret=interpret,
                                      counters=counters)
    else:
        with _trace.span("dispatch", backend=p.backend, path=p.path):
            res = _execute_engine(p, groups, keys, n_valid, tile=tile,
                                  interpret=interpret)

    if collect_stats:
        stats = dict(res.stats) if res.stats else {}
        stats["tuples"] = n
        stats["num_shards"] = p.num_shards
        res = res._replace(stats=stats)
        _observe_throughput(p, res, n, t0)
    return res, None


def _state_collects_stats(state) -> bool:
    """Whether a streaming state is the ``(state, counters)`` wrapping of
    ``collect_stats=True`` (a dict second element — no engine state ever
    threads one)."""
    return (isinstance(state, tuple) and len(state) == 2
            and isinstance(state[1], dict))


def _observe_throughput(p: Plan, res: AggResult, tuples: int,
                        t0: float) -> None:
    """Record one observed-throughput sample in the process registry —
    only for concrete results (under a jit trace the clock would measure
    trace time, and the sample would poison the routing table)."""
    from repro.obs.registry import METRICS, plan_fingerprint
    leaves = jax.tree_util.tree_leaves((res.groups, res.values))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return
    jax.block_until_ready(leaves)
    METRICS.observe(p.backend, plan_fingerprint(p), tuples=int(tuples),
                    seconds=_time.perf_counter() - t0)
