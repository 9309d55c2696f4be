"""Two-phase (mergeable-state) query execution across a device mesh.

The paper composes one scan topology out of mergeable per-range states (the
``n`` entities' distributed rules, e.g. the dc boundary-subtract).  This
module runs that same algebra *across devices*: every ``Query`` executes as

    partition -> local (per shard) -> merge (combine tree) -> finalize

where the local phase reduces each shard's range of the stream to a compact
:class:`repro.core.engine.PartialTable` (or a sorted run for the
non-incremental operators) and only those cross device boundaries.  The
combine tree — log2(S) rounds of pairwise
:func:`repro.core.engine.combine_partial_tables` — is the device-level
analog of the paper's merge network; the "gather-then-merge" layout here
leaves collective placement to XLA's SPMD partitioner (the local phase runs
under ``shard_map`` when a :class:`jax.sharding.Mesh` is given, and the
merged tables are tiny next to the stream).

Two merge channels, chosen per op:

  * **table channel** — mergeable combiners: per-group partial states
    folded with ``Combiner.merge_partial`` (the dc boundary rule merges
    adjacent ranges of the (group, key)-sorted stream exactly);
  * **run channel** — the non-incremental tail (median) and, for windowed
    queries, every op the single-device pane path also serves from the
    merged window: per-shard (group, key)-sorted runs merged with the
    bitonic merge network (:func:`repro.core.sorter.merge_presorted`), then
    the ordinary window tails.  A fully sorted sequence of a multiset is
    unique, so this channel is bit-identical to single-device execution by
    construction.

Shard-count semantics: ``num_shards`` without a mesh runs the identical
two-phase pipeline on one device (``vmap`` locals) — the algebra is
testable anywhere; with a mesh the local phase is SPMD over the mesh's
flattened axes (host-platform CPU meshes via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` included, as
``launch/dryrun.py`` does).  Per-shard backends still come from the
registry probe (:func:`repro.kernels.registry.choose_backend` consulted
with the mesh's devices): kernel backends keep their per-shard Pallas
kernels unchanged.  On the *reference* backend the local phase is SPMD
(``shard_map``); the kernel-backend local phases currently run their
per-shard kernels as a sequential gather-then-merge loop on the default
device — same two-phase algebra and results, device placement pending
(ROADMAP: "device-placed kernel local phases").  The grouped kernel
backend also merges with its kernel (one group-by pass over the packed
shard tables, :func:`_kernel_merge_tables`) instead of the combine tree.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core import sorter
from repro.core import streaming as _streaming
from repro.core.combiners import Combiner

# the package attribute ``repro.core.swag`` is shadowed by the deprecated
# ``swag`` entry-point function, so resolve the *module* explicitly
_swag = importlib.import_module("repro.core.swag")

Array = jax.Array
PAD_GROUP = _engine.PAD_GROUP

#: ops whose Pallas group-by kernel output *is* the partial state
#: (single-array state, identity finalize) — the kernel-backend local phase
KERNEL_STATE_OPS = _swag.PARTIAL_OPS

#: the cross-shard watermark rule (re-export): a sharded stream's watermark
#: is the minimum over its shards' watermarks
from repro.core.eventtime import merge_watermarks  # noqa: E402,F401


def mesh_num_shards(mesh) -> int:
    """Total devices of ``mesh`` — the shard count of its flattened axes."""
    return int(mesh.devices.size)


def partition_stream(groups: Array, keys: Array, num_shards: int):
    """[N] -> [S, N/S] contiguous shard slices (adjacent ranges, which is
    what keeps the dc boundary rule exact on sorted streams)."""
    n = groups.shape[-1]
    if n % num_shards:
        raise ValueError(
            f"sharded execution needs num_shards to divide the stream "
            f"length, got n={n} num_shards={num_shards}")
    length = n // num_shards
    return (groups.reshape(num_shards, length),
            keys.reshape(num_shards, length))


def _map_shards(fn, mesh, args):
    """Run ``fn`` (written for one shard's slice) over the leading shard
    axis of every array in ``args``: ``vmap`` on one device, ``shard_map``
    over the mesh's flattened axes when a mesh is given."""
    if mesh is None:
        return jax.vmap(fn)(*args)
    spec = jax.sharding.PartitionSpec(tuple(mesh.axis_names))

    def body(*a):
        return jax.vmap(fn)(*a)

    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(*args)


def combine_tree(tables: _engine.PartialTable, ops, *, key_dtype,
                 counters=None):
    """Merge stacked per-shard tables (leading axis = shard) down to one —
    log2(S) rounds of pairwise merges, widths doubling each round.

    Shard counts that are not powers of two are padded with
    :func:`repro.core.engine.empty_partial_table` (the merge identity), so
    the tree stays balanced and every round is one ``vmap``'d node type.

    With ``counters`` (an :mod:`repro.obs.counters` dict) returns
    ``(table, counters)``, recording per round: the merged table row width
    (static — the additive-growth hypothesis from the ROADMAP, measured),
    the live groups summed over the round's nodes (dynamic), and the bytes
    of partial-table state the round's merges produced (static — a proxy
    for cross-device traffic).
    """
    s = tables.groups.shape[0]
    width = tables.groups.shape[1]
    s2 = sorter.next_pow2(s)
    if s2 != s:
        pad = _engine.empty_partial_table(width, ops, key_dtype)
        pad = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (s2 - s,) + x.shape), pad)
        tables = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), tables, pad)
        s = s2
    round_width: list = []
    round_groups: list = []
    round_bytes: list = []
    while s > 1:
        a = jax.tree.map(lambda x: x[0::2], tables)   # earlier ranges
        b = jax.tree.map(lambda x: x[1::2], tables)
        tables = jax.vmap(
            lambda ta, tb: _engine.combine_partial_tables(
                ta, tb, ops, key_dtype=key_dtype))(a, b)
        s //= 2
        if counters is not None:
            round_width.append(tables.groups.shape[1])
            round_groups.append(jnp.sum(tables.num_groups))
            round_bytes.append(_table_bytes(tables))
    out = jax.tree.map(lambda x: x[0], tables)
    if counters is None:
        return out
    return out, _put_round_counters(counters, round_width, round_groups,
                                    round_bytes)


def _table_bytes(table) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(table)
               if hasattr(x, "dtype"))


def _put_round_counters(counters, widths, groups, nbytes):
    """Record the merge stage's per-round telemetry (see
    :func:`combine_tree`)."""
    from repro.obs import counters as _c
    counters = _c.put(counters, "combine_rounds",
                      jnp.asarray(len(widths), jnp.int32))
    counters = _c.put(counters, "combine_round_width",
                      jnp.asarray(widths, jnp.int32))
    counters = _c.put(counters, "combine_round_groups",
                      (jnp.stack(groups) if groups
                       else jnp.zeros((0,), jnp.int32)))
    return _c.put(counters, "combine_round_bytes",
                  jnp.asarray(nbytes, jnp.float32))


def _trim_table(table: _engine.PartialTable, width: int
                ) -> _engine.PartialTable:
    """Cut a merged table back to ``width`` rows.  Safe whenever ``width``
    is at least the possible number of real groups (e.g. the stream
    length): rows past it are PAD padding introduced by the pow2 shard
    padding of :func:`combine_tree`, and trimming keeps every output column
    the same length as its single-device counterpart."""
    return jax.tree.map(
        lambda x: x[:width] if x.ndim >= 1 else x, table)


def merge_sorted_runs(run_groups: Array, run_keys: Array):
    """[S, L] per-shard (group, key)-sorted runs -> one sorted [S*L] run —
    the run channel's combine tree (``merge_presorted`` *is* the log2(S)
    rounds of pairwise bitonic merges).  S and L must be powers of two
    (padded by the callers)."""
    s, length = run_groups.shape
    return sorter.merge_presorted(
        (run_groups.reshape(-1), run_keys.reshape(-1)),
        run=length, num_keys=2)


def _pad_pow2_shards(gs: Array, ks: Array):
    """Pad [S, L] shard runs to power-of-two S and L with PAD_GROUP rows
    (they sort after every real group and stay masked downstream)."""
    s, length = gs.shape
    s2, l2 = sorter.next_pow2(s), sorter.next_pow2(length)
    if (s2, l2) != (s, length):
        pg = jnp.full((s2, l2), PAD_GROUP, gs.dtype)
        pk = jnp.zeros((s2, l2), ks.dtype)
        gs = pg.at[:s, :length].set(gs)
        ks = pk.at[:s, :length].set(ks)
    return gs, ks


# --------------------------------------------------------------------------
# non-windowed (engine) path
# --------------------------------------------------------------------------

def _local_engine_tables(q, gs, ks, nvs, combiner_ops, mesh, backend, *,
                         tile, interpret):
    """Per-shard local phase of the engine path: partial tables over the
    shard slices.  Kernel backends run their (unchanged) per-shard group-by
    kernel — possible exactly when every op's kernel output *is* its
    partial state (KERNEL_STATE_OPS); plan() guarantees that here.  The
    kernel loop is gather-then-merge on the default device (not yet placed
    per mesh device — see the module docstring), unlike the reference
    branch below, which is SPMD under ``shard_map``."""
    if backend == "pallas":
        from repro.kernels.groupagg.ops import _groupagg_kernel_exec
        tables = []
        for s in range(gs.shape[0]):
            states = {}
            shared = None
            for op in combiner_ops:
                name = op.name if isinstance(op, Combiner) else op
                r = _groupagg_kernel_exec(
                    gs[s], ks[s], op, n_valid=None if nvs is None else nvs[s],
                    tile=tile, interpret=interpret)
                states[name] = r.values
                shared = shared or (r.groups, r.valid, r.num_groups)
            tables.append(_engine.PartialTable(shared[0], states, shared[1],
                                               shared[2]))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *tables)

    def local(g, k, nv=None):
        return _engine.multi_engine_partials(g, k, combiner_ops, n_valid=nv)

    args = (gs, ks) if nvs is None else (gs, ks, nvs)
    return _map_shards(local, mesh, args)


def _kernel_merge_tables(tables: _engine.PartialTable, *, tile, interpret
                         ) -> _engine.PartialTable:
    """Merge stage of the kernel backend: one group-by kernel pass per op.

    The shards are adjacent ranges of the group-sorted stream, so their
    tables' live rows, packed in shard order, form one group-sorted run in
    which only a group that spans a shard boundary repeats.  Folding that
    run with each op's :func:`repro.core.combiners.partial_combiner` (for
    KERNEL_STATE_OPS: sum/min/max of the states, counts summed) is the
    whole merge — the same kernel as the local phase, instead of the
    reference engine's combine tree.  The packed run is as wide as the
    stream, which is the single-device layout."""
    from repro.kernels.groupagg.ops import _groupagg_kernel_exec

    s, width = tables.groups.shape
    n = s * width
    offsets = jnp.cumsum(tables.num_groups) - tables.num_groups
    lanes = jnp.arange(width)[None, :]
    dest = jnp.where(lanes < tables.num_groups[:, None],
                     offsets[:, None] + lanes, n).reshape(-1)

    def pack(x, fill):
        return jnp.full((n + 1,), fill, x.dtype).at[dest].set(
            x.reshape(-1), mode="drop")[:n]

    groups = pack(tables.groups, PAD_GROUP)
    states = {}
    shared = None
    for name, state in tables.states.items():
        r = _groupagg_kernel_exec(groups, pack(state, 0),
                                  _partial_combiner(name), tile=tile,
                                  interpret=interpret)
        states[name] = r.values
        shared = shared or (r.groups, r.valid, r.num_groups)
    return _engine.PartialTable(shared[0], states, shared[1], shared[2])


@functools.lru_cache(maxsize=None)
def _partial_combiner(name: str):
    """One table-level combiner object per op name: the kernel entry point
    takes it as a static argument, so a fresh one per call would
    recompile the kernel every time."""
    from repro.core.combiners import get_combiner, partial_combiner
    return partial_combiner(get_combiner(name))


def _engine_sharded(q, groups, keys, n_valid, *, num_shards, mesh, backend,
                    tile, interpret, counters=None):
    from repro.obs import trace as _trace
    names = q.op_names
    combiner_ops = tuple(op for op, nm in zip(q.ops, names) if nm != "median")

    n = groups.shape[-1]
    groups = groups.astype(jnp.int32)
    with _trace.span("partition"):
        if n_valid is not None:
            # mask the tail up front so every shard slice keeps the engine's
            # sorted-with-PAD-tail contract locally
            groups = jnp.where(jnp.arange(n) < n_valid, groups, PAD_GROUP)
        gs, ks = partition_stream(groups, keys, num_shards)
    length = n // num_shards
    nvs = None
    if n_valid is not None:
        nvs = jnp.clip(n_valid - jnp.arange(num_shards) * length, 0, length)

    values: dict = {}
    shared = None
    if combiner_ops:
        with _trace.span("local"):
            tables = _local_engine_tables(q, gs, ks, nvs, combiner_ops, mesh,
                                          backend, tile=tile,
                                          interpret=interpret)
        with _trace.span("merge"):
            if backend == "pallas":
                table = _kernel_merge_tables(tables, tile=tile,
                                             interpret=interpret)
                if counters is not None:
                    counters = _put_round_counters(
                        counters, [n], [table.num_groups],
                        [_table_bytes(table)])
            elif counters is None:
                table = combine_tree(tables, combiner_ops,
                                     key_dtype=keys.dtype)
            else:
                table, counters = combine_tree(tables, combiner_ops,
                                               key_dtype=keys.dtype,
                                               counters=counters)
            # pow2 shard padding can leave the merged table wider than the
            # stream; trim so every column matches the single-device layout
            # (real groups never exceed the stream length)
            table = _trim_table(table, n)
        with _trace.span("finalize"):
            g_out, vals, valid, num = _engine.finalize_partial_table(
                table, combiner_ops)
        values.update(vals)
        shared = (g_out, valid, num)

    if "median" in names:
        # run channel: the shard slices are adjacent ranges of the globally
        # (group, key)-sorted stream, so their bitonic merge reproduces the
        # exact input stream the single-device rank pick reads
        with _trace.span("merge", channel="runs"):
            mg, mk = merge_sorted_runs(*_pad_pow2_shards(gs, ks))
            mg, mk = mg[:n], mk[:n]
            t = _swag._median_sorted_window(mg, mk, interpolate=q.interpolate,
                                            n_valid=n_valid)
        values["median"] = jnp.where(t.valid, t.medians,
                                     jnp.zeros((), t.medians.dtype))
        shared = shared or (t.groups, t.valid, t.num_groups)
    if counters is None:
        return shared[0], values, shared[1], shared[2]
    return shared[0], values, shared[1], shared[2], counters


# --------------------------------------------------------------------------
# windowed (SWAG) path
# --------------------------------------------------------------------------

def _window_sharded(q, groups, keys, *, num_shards, mesh, backend,
                    use_xla_sort, interpret):
    w = q.window
    ws, wa = w.ws, w.wa
    n = groups.shape[-1]
    nw = _swag.num_windows(n, ws, wa)
    names = q.op_names

    if backend in ("pallas", "pallas-panes") or nw == 0 \
            or not (_swag.pane_compatible(ws, wa)
                    or (ws == wa and ws & (ws - 1) == 0)) \
            or w.panes is False:
        return _window_partitioned(q, groups, keys, num_shards=num_shards,
                                   backend=backend,
                                   use_xla_sort=use_xla_sort,
                                   interpret=interpret)

    p = ws // wa
    np_ = nw + p - 1
    pg = _swag.frame_panes(groups.astype(jnp.int32), wa, np_)
    pk = _swag.frame_panes(keys, wa, np_)
    # pad the pane axis so every shard owns the same number of panes
    npp = -(-np_ // num_shards) * num_shards
    if npp != np_:
        pad_g = jnp.full((npp - np_, wa), PAD_GROUP, pg.dtype)
        pad_k = jnp.zeros((npp - np_, wa), pk.dtype)
        pg = jnp.concatenate([pg, pad_g])
        pk = jnp.concatenate([pk, pad_k])

    # the single-device pane dispatch, verbatim (shared predicate — the
    # bit-identical guarantee rests on both paths routing ops the same
    # way): incremental ops keep the compact-table channel, everything
    # else (median, mean, dc, float-reordering sums, ...) rides the
    # merged sorted window
    table_sel = _swag.pane_table_channel(q.ops, keys.dtype, p)
    table_ops = tuple(op for op, sel in zip(q.ops, table_sel) if sel)
    run_pairs = tuple((op, name) for (op, name), sel
                      in zip(zip(q.ops, names), table_sel) if not sel)

    if table_ops:
        def local(g, k):
            return _swag.pane_partials(g, k, table_ops,
                                       use_xla_sort=use_xla_sort)

        sg, sk, tables = _map_shards(local, mesh, (pg, pk))
        tables = jax.tree.map(lambda x: x[:np_], tables)
    else:
        # run-channel-only query: the local phase is just the pane sort
        srt = sorter.sort_pairs_xla if use_xla_sort else sorter.sort_pairs

        def local(g, k):
            return srt(g, k, full_width=True)

        sg, sk = _map_shards(local, mesh, (pg, pk))
    sg, sk = sg[:np_], sk[:np_]

    widx = jnp.arange(nw)[:, None] + jnp.arange(p)[None, :]

    values: dict = {}
    shared = None
    if table_ops:
        # per-window combine tree over the window's P pane tables
        wt = jax.tree.map(lambda x: x[widx], tables)   # [NW, P, WA, ...]
        merged = jax.vmap(
            lambda t: combine_tree(t, table_ops, key_dtype=keys.dtype))(wt)
        tg, tvals, tvalid, tnum = jax.vmap(
            lambda t: _engine.finalize_partial_table(t, table_ops))(merged)
        values.update(tvals)
        shared = (tg, tvalid, tnum)

    if run_pairs:
        wg = _swag._pane_windows(sg, nw, p)
        wk = _swag._pane_windows(sk, nw, p)

        def per_window(g, k):
            if p > 1:
                g, k = sorter.merge_presorted((g, k), run=wa, num_keys=2)
            return _swag.window_tails(g, k, run_pairs,
                                      interpolate=q.interpolate)

        mg, mvalues, mvalid, mnum = jax.vmap(per_window)(wg, wk)
        values.update(mvalues)
        shared = (mg, mvalid, mnum)

    return shared[0], values, shared[1], shared[2]


def _window_partitioned(q, groups, keys, *, num_shards, backend,
                        use_xla_sort, interpret):
    """Fallback windowed sharding: partition the *window axis* — each shard
    computes a contiguous block of complete windows from its slice of the
    stream with its probe-selected backend (per-shard kernels unchanged),
    and the merge stage is a window-axis concatenation.  Serves the
    non-pane-compatible shapes and the kernel backends.  Runs
    gather-then-merge on the default device (see the module docstring);
    windows are independent work items, so device placement is a pure
    plumbing follow-up."""
    w = q.window
    ws, wa = w.ws, w.wa
    n = groups.shape[-1]
    nw = _swag.num_windows(n, ws, wa)
    names = q.op_names

    wps = -(-nw // num_shards) if nw else 0   # windows per shard
    if wps == 0:
        num_shards = 1
        wps = nw
    slice_len = (max(wps, 1) - 1) * wa + ws
    starts = jnp.arange(num_shards) * wps * wa
    idx = starts[:, None] + jnp.arange(slice_len)[None, :]
    in_range = idx < n
    idx = jnp.clip(idx, 0, max(n - 1, 0))
    gs = jnp.where(in_range, groups[idx], PAD_GROUP).astype(jnp.int32)
    ks = jnp.where(in_range, keys[idx], jnp.zeros((), keys.dtype))

    outs = []
    for s in range(num_shards):
        if backend in ("pallas", "pallas-panes"):
            from repro.kernels.swag.ops import _swag_kernel_exec
            panes = True if backend == "pallas-panes" else False
            og, ovs, valid, oc = _swag_kernel_exec(
                gs[s], ks[s], ws=ws, wa=wa, ops=names,
                interpret=interpret, panes=panes)
        else:
            og, ovs, valid, oc = _swag.swag_multi(
                gs[s], ks[s], ws=ws, wa=wa, ops=q.ops,
                interpolate=q.interpolate, use_xla_sort=use_xla_sort,
                panes=q.window.panes)
        outs.append((og, ovs, valid, oc))

    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
    return jax.tree.map(lambda x: x[:nw], cat)


# --------------------------------------------------------------------------
# streaming path
# --------------------------------------------------------------------------

def stream_push_eventtime_sharded(q, groups, keys, timestamps, state, *,
                                  num_shards, mesh=None, n_valid=None,
                                  p_ports: int = 4, counters=None):
    """One sharded event-time push: per-shard bounded-lateness reorder
    buffers (stacked leading axis — each shard tracks its own watermark),
    released against the **min-merged** global watermark
    (:func:`repro.core.eventtime.merge_watermarks`: a tuple may still
    arrive on the slowest shard), then one shared time-pane store.

    The released emissions of all shards are merged into one
    timestamp-ordered batch (``lax.sort`` with the flat lane index as the
    tie-break — deterministic for any shard interleaving) before the store
    ingest; the push then emits the closed hopping windows the global
    watermark has passed (:func:`repro.core.panestore.emit_time_windows`).
    Returns the streaming port tuple + new state, shaped like the
    single-shard reorder route's (plus the counters dict when ``counters``
    is given — reorder depth/forced pops reduced over shards, pane-store
    evictions/occupancy, late drops, windows emitted, watermark lag).
    """
    from repro import query as _q
    from repro.core import eventtime as _et
    from repro.core import panestore as _ps
    from repro.core import panetable as _pt
    w = q.window
    rspec = w.reorder_spec()
    spec = w.store_spec()
    rstates, pstate, next_end = state

    n = groups.shape[-1]
    groups = groups.astype(jnp.int32)
    keys = jnp.asarray(keys, pstate.keys.dtype)
    ts = jnp.asarray(timestamps, jnp.int32)
    gs, ks = partition_stream(groups, keys, num_shards)
    tss = ts.reshape(num_shards, n // num_shards)
    length = n // num_shards
    nvs = None
    live = jnp.ones((num_shards, length), bool)
    if n_valid is not None:
        nvs = jnp.clip(n_valid - jnp.arange(num_shards) * length, 0, length)
        live = jnp.arange(length)[None, :] < nvs[:, None]

    # the release gate: every shard's post-push watermark, min-merged —
    # computed up front (cheap max) so this push's releases already respect
    # the other shards' progress.  Lateness is judged against the *previous*
    # push's merged watermark: the contiguous slicing hands one shard the
    # tail of every batch (inflated local maximum), and a tuple is only
    # unrecoverable once an already-emitted evaluation has passed it.
    prev_wm = _et.merge_watermarks(rstates.max_ts - w.max_lateness)
    new_max = jnp.maximum(rstates.max_ts,
                          jnp.max(jnp.where(live, tss, _et.TS_MIN), axis=-1))
    global_wm = _et.merge_watermarks(new_max - w.max_lateness)

    per_shard = None
    if counters is not None:
        # fresh per-shard reorder counters each push; vmap batches them,
        # and the cross-shard reduction below folds them into the carry
        per_shard = {"reorder_depth_hwm": jnp.zeros((), jnp.int32),
                     "reorder_forced_pops": jnp.zeros((), jnp.int32)}

    if nvs is None:
        def shard_push(rst, t, g, k):
            return _et.reorder_push(rspec, rst, t, g, k,
                                    release_wm=prev_wm, late_wm=prev_wm,
                                    drain_wm=global_wm, counters=per_shard)
        out = jax.vmap(shard_push)(rstates, tss, gs, ks)
    else:
        def shard_push(rst, t, g, k, nv):
            return _et.reorder_push(rspec, rst, t, g, k, n_valid=nv,
                                    release_wm=prev_wm, late_wm=prev_wm,
                                    drain_wm=global_wm, counters=per_shard)
        out = jax.vmap(shard_push)(rstates, tss, gs, ks, nvs)
    if counters is None:
        emits, rstates = out
    else:
        from repro.obs import counters as _c
        emits, rstates, shard_cnt = out
        counters = _c.high_water(counters, "reorder_depth_hwm",
                                 jnp.max(shard_cnt["reorder_depth_hwm"]))
        counters = _c.bump(counters, "reorder_forced_pops",
                           jnp.sum(shard_cnt["reorder_forced_pops"]))

    sg, sk, sts, slive = merge_emissions(emits)
    if counters is None:
        pstate = _ps.push_time(spec, pstate, sg, sk, sts, live=slive,
                               retire_below=next_end - w.range)
    else:
        pstate, counters = _ps.push_time(spec, pstate, sg, sk, sts,
                                         live=slive,
                                         retire_below=next_end - w.range,
                                         counters=counters)
    out, next_end = _ps.emit_time_windows(
        spec, pstate, q.ops, next_end, global_wm, w.table_spec().rows,
        interpolate=q.interpolate)
    ports = _q.time_ports(_pt.Emission(*out), p_ports)
    if counters is not None:
        counters = _c.put(counters, "late_dropped", jnp.sum(rstates.dropped))
        counters = _c.put(counters, "watermark", global_wm)
        # how far the fastest shard runs ahead of the merged release gate —
        # the skew the min-merge rule is absorbing
        counters = _c.put(counters, "watermark_lag",
                          jnp.max(new_max - w.max_lateness) - global_wm)
        counters = _c.bump(counters, "windows_emitted", jnp.sum(
            (ports[3] > 0).astype(jnp.int32)))
    if counters is None:
        return ports, (rstates, pstate, next_end)
    return ports, (rstates, pstate, next_end), counters


def merge_emissions(emits):
    """Flatten stacked per-shard :class:`repro.core.eventtime.ReorderEmit`
    batches into one timestamp-ordered stream (dead lanes sort to the
    tail; the flat lane index breaks timestamp ties deterministically).
    Returns ``(groups, keys, ts, live)``."""
    e_ts = emits.ts.reshape(-1)
    e_g = emits.groups.reshape(-1)
    e_k = emits.keys.reshape(-1)
    e_live = emits.live.reshape(-1)
    big = jnp.iinfo(jnp.int32).max
    ts_key = jnp.where(e_live, e_ts, big)
    lane = jnp.arange(e_ts.shape[0], dtype=jnp.int32)
    sts, _, sg, sk, sl = jax.lax.sort(
        (ts_key, lane, e_g, e_k, e_live.astype(jnp.int32)), num_keys=2)
    slive = sl == 1
    return sg, sk, jnp.where(slive, sts, 0), slive


def stream_push_sharded(q, groups, keys, carries, combiners, *,
                        num_shards, mesh=None, n_valid=None,
                        p_ports: int = 4, counters=None):
    """One sharded rolling push: per-shard partial tables, one combine
    tree, then the carry/emit bookkeeping of
    :func:`repro.core.streaming.stream_push_table`.  Bit-identical to the
    single-device :func:`repro.core.streaming.stream_push` for
    exactly-mergeable ops.  With ``counters`` returns
    ``(ports, carries, counters)`` recording the per-round combine-tree
    telemetry plus the pushed tuple count."""
    n = groups.shape[-1]
    groups = groups.astype(jnp.int32)
    first_group = groups[0]
    if n_valid is not None:
        groups = jnp.where(jnp.arange(n) < n_valid, groups, PAD_GROUP)
        any_real = n_valid > 0
    else:
        any_real = jnp.asarray(True)
    gs, ks = partition_stream(groups, keys, num_shards)

    def local(g, k):
        return _engine.multi_engine_partials(g, k, combiners)

    tables = _map_shards(local, mesh, (gs, ks))
    if counters is None:
        table = combine_tree(tables, combiners, key_dtype=keys.dtype)
    else:
        from repro.obs import counters as _c
        table, counters = combine_tree(tables, combiners,
                                       key_dtype=keys.dtype,
                                       counters=counters)
        pushed = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
        counters = _c.bump(counters, "stream_tuples", pushed)
    table = _trim_table(table, n)   # pow2 padding -> back to N+1 out slots
    out, new_carries = _streaming.stream_push_table(
        table, carries, combiners, first_group=first_group,
        any_real=any_real, p_ports=p_ports)
    if counters is None:
        return out, new_carries
    return out, new_carries, counters
