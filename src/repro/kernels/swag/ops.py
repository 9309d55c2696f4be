"""jit'd execution layer for the fused SWAG kernels.

:func:`_swag_kernel_exec` is the internal (non-deprecated) entry the backend
registry dispatches to — it accepts one op or a tuple of ops and runs the
fused multi-op kernels (pane framing / sorting once, N combiner tails).

Dispatch (``panes=None``): when ``WS % WA == 0``, both powers of two and
``WA < WS``, the pane pair runs — panes sorted once in a prologue
``pallas_call`` (grid over panes), windows assembled by merging their
``P = WS/WA`` presorted panes in VMEM (grid over windows) — amortising the
sort across the P windows sharing each pane.  Otherwise each window is
re-sorted from scratch.  Results are element-exact either way: a fully
(group, key)-sorted window is unique, so both paths feed the identical
sequence to the identical engine tail.

:func:`swag_tpu` is kept as a thin deprecated shim over
``repro.query.Query`` + ``execute``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import PAD_GROUP, _deprecated
from repro.core.panestore import _key_sentinel
from repro.core.swag import frame_panes, frame_windows, num_windows, \
    resolve_panes
from repro.kernels import common as _common
from repro.obs.trace import stage


class SwagResult(NamedTuple):
    groups: jax.Array   # [NW, WS]
    values: jax.Array   # [NW, WS]
    valid: jax.Array    # [NW, WS]
    num_groups: jax.Array  # [NW]


@functools.partial(jax.jit,
                   static_argnames=("ws", "wa", "ops", "interpret", "panes"))
def _swag_kernel_exec(groups, keys, *, ws: int, wa: int, ops,
                      interpret: bool | None = None,
                      panes: bool | None = None):
    """Fused Pallas SWAG over one or many ops.

    ``ops``: one combiner name or a tuple of names (``"median"`` allowed).
    WS must be a power of two (pad otherwise).  ``panes`` forces (True) or
    suppresses (False) the sort-once pane path; ``None`` auto-dispatches.
    Returns ``(og [NW, WS], {name: ov}, valid [NW, WS], oc [NW])``.
    """
    interpret = _common.default_interpret(interpret)
    if ws & (ws - 1):
        raise ValueError(f"WS must be a power of two, got {ws}")
    from repro.kernels.swag import kernel as _k

    names = (ops,) if isinstance(ops, str) else tuple(ops)
    nw = num_windows(groups.shape[-1], ws, wa)
    if nw == 0:
        # stream shorter than one window: agree with the reference backend
        # (an empty [0, WS] result) instead of handing pallas_call a
        # zero-length grid
        return (jnp.full((0, ws), PAD_GROUP, jnp.int32),
                {name: jnp.zeros((0, ws), _k._out_dtype(name, keys.dtype))
                 for name in names},
                jnp.zeros((0, ws), bool), jnp.zeros((0,), jnp.int32))
    panes = resolve_panes(ws, wa, groups.shape[-1], panes)

    # wa == ws means one pane per window: the "merge" degenerates to the
    # plain per-window sort, which is exactly the classic fused kernel.
    if panes and wa < ws:
        p = ws // wa
        np_ = nw + p - 1
        with stage("frame"):
            pg = frame_panes(groups.astype(jnp.int32), wa, np_)
            pk = frame_panes(keys, wa, np_)
        with stage("sort_panes"):
            pg, pk = _k.sort_panes_pallas(pg, pk, interpret=interpret)
        with stage("pane_merge"):
            og, ovs, oc = _k.swag_pallas_panes(pg, pk, ops, p=p,
                                               interpret=interpret)
    else:
        with stage("frame"):
            fg = frame_windows(groups.astype(jnp.int32), ws, wa)
            fk = frame_windows(keys, ws, wa)
        with stage("window_sort"):
            og, ovs, oc = _k.swag_pallas(fg, fk, ops, interpret=interpret)
    with stage("assemble"):
        valid = jnp.arange(ws)[None, :] < oc[:, None]
        og = jnp.where(valid, og, PAD_GROUP)
    return og, ovs, valid, oc


@functools.partial(jax.jit, static_argnames=("ops", "interpret"))
def _timeframe_kernel_exec(frames_g, frames_k, *, ops,
                           interpret: bool | None = None):
    """Fused Pallas tail for **time-range windows** (the replay strategy):
    the event-time layer has already framed the ts-sorted stream into
    ``[NW, wcap]`` rows (``repro.core.eventtime.frame_time_windows``;
    variable tuple counts, dead lanes PAD-masked), so each grid row runs
    the same in-VMEM sort + multi-op tail as :func:`_swag_kernel_exec`'s
    re-sort path.  Returns ``(og, {name: ov}, valid, oc)``."""
    interpret = _common.default_interpret(interpret)
    from repro.kernels.swag import kernel as _k

    names = (ops,) if isinstance(ops, str) else tuple(ops)
    nw, wcap = frames_g.shape
    if wcap & (wcap - 1):
        raise ValueError(f"time frames must be power-of-two wide, "
                         f"got {wcap}")
    if nw == 0:
        return (jnp.full((0, wcap), PAD_GROUP, jnp.int32),
                {name: jnp.zeros((0, wcap),
                                 _k._out_dtype(name, frames_k.dtype))
                 for name in names},
                jnp.zeros((0, wcap), bool), jnp.zeros((0,), jnp.int32))
    og, ovs, oc = _k.swag_pallas(frames_g.astype(jnp.int32), frames_k,
                                 names, interpret=interpret)
    valid = jnp.arange(wcap)[None, :] < oc[:, None]
    og = jnp.where(valid, og, PAD_GROUP)
    return og, ovs, valid, oc


@functools.partial(jax.jit, static_argnames=("spec", "ops", "interpret"))
def _swag_pergroup_kernel_exec(groups, keys, *, spec, ops,
                               interpret: bool | None = None,
                               counters=None):
    """Per-group-window SWAG with the replay offloaded to Pallas.  The
    store *placement* bookkeeping always runs in XLA; the kernel side has
    two regimes, routed by :func:`repro.core.panestore.partial_path_names`:

    * **partial-fused** (every op on the partial path): ONE
      ``pallas_call`` over the whole stream — the ring buffers live in
      VMEM scratch across the sequential chunk grid, each step fusing the
      store update (writes + close-sort epilogue) with the per-slot
      partial aggregates.  No per-chunk store round trip through HBM; the
      fold of slot partials into per-group values runs in XLA after it.
    * **merge-replay** (median/distinct_count present, or float
      sum/mean): the classic gather path — store push + pane gather in
      XLA, one ``pallas_call`` (grid over evaluation x group rows) for
      merge + shared butterfly compaction + N operator tails.

    ``spec`` is a static :class:`repro.core.panestore.PaneStoreSpec`;
    ``ops`` a tuple of DIRECT_OPS names.  Returns
    ``(og [NE, C], {name: ov}, valid [NE, C], num_groups [NE])``, and with
    ``counters`` (an :mod:`repro.obs.counters` dict) the counters after
    them: the store's evictions and occupancy high-water mark, and on the
    partial-fused path ``pane_dir_fallbacks``.
    """
    from repro.core import panestore as _ps
    from repro.core.swag import per_group_chunk_scan, pergroup_write_plan
    from repro.kernels.swag import kernel as _k

    interpret = _common.default_interpret(interpret)
    names = (ops,) if isinstance(ops, str) else tuple(ops)
    ne = groups.shape[-1] // spec.wa
    c = spec.capacity
    if ne == 0:
        out = (jnp.full((0, c), PAD_GROUP, jnp.int32),
               {name: jnp.zeros((0, c), _k._pergroup_out_dtype(
                   name, keys.dtype)) for name in names},
               jnp.zeros((0, c), bool), jnp.zeros((0,), jnp.int32))
        if counters is None:
            return out
        from repro.obs import counters as _c
        return out + (_c.ensure(counters, ("pane_evictions",
                                           "pane_occupancy_hwm",
                                           "pane_dir_fallbacks")),)

    psel = _ps.partial_path_names(names, keys.dtype)
    if psel and all(psel):
        wp = pergroup_write_plan(spec, groups, counters=counters)
        if counters is not None:
            wp, counters = wp
        slots, lanes, seqs, own_s, cnt_s, lo_s, sortmask, ugroups, num = wp
        with stage("frame"):
            ck = frame_panes(keys, spec.wa, ne)
        with stage("slot_partials"):
            parts = _k.pergroup_slot_partials_pallas(
                ck, slots, lanes, seqs, own_s, cnt_s, lo_s, sortmask, names,
                interpret=interpret)
        with stage("slot_fold"):
            ovs = _combine_slot_partials(own_s, ugroups, parts, names,
                                         keys.dtype)
        groups_out = ugroups
    else:
        state = _ps.init_store(spec, keys.dtype)
        scanned = per_group_chunk_scan(
            spec, state, groups, keys, lambda st: _ps.gather_runs(spec, st),
            counters=counters)
        if counters is None:
            _state, runs = scanned
        else:
            _state, runs, counters = scanned
        length = runs.run_keys.shape[-1]
        with stage("replay"):
            ovs = _k.pergroup_replay_pallas(
                runs.run_keys.reshape(ne * c, length),
                runs.run_valid.reshape(ne * c, length).astype(jnp.int32),
                names, run=spec.wa, interpret=interpret)
        groups_out, num = runs.groups, runs.num_groups

    with stage("assemble"):
        valid = jnp.arange(c)[None, :] < num[:, None]
        values = {name: jnp.where(valid, v.reshape(ne, c),
                                  jnp.zeros((), v.dtype))
                  for name, v in ovs.items()}
        og = jnp.where(valid, groups_out, PAD_GROUP)
    if counters is None:
        return og, values, valid, num
    return og, values, valid, num, counters


def _combine_slot_partials(own_s, ugroups, parts, names, key_dtype):
    """Per-slot partials ``{part: [NE, C]}`` -> per-group values
    ``{name: [NE, C]}`` in the evaluation directory's row order: each
    directory row ``u`` folds the slots its group owns (the per-pane
    partial formulas of :func:`repro.core.panestore._replay_partials`).
    Chunks are folded in batches so the ``[C, C]`` ownership masks stay
    bounded."""
    hi = _key_sentinel(key_dtype)
    lo_sent = (jnp.iinfo(key_dtype).min
               if jnp.issubdtype(key_dtype, jnp.integer) else -jnp.inf)

    def chunk(xs):
        owner, ug, part = xs
        rows = ((ug[:, None] == owner[None, :]) & (owner != PAD_GROUP)[None, :]
                & (ug != PAD_GROUP)[:, None])
        cnt = jnp.sum(jnp.where(rows, part["count"][None, :], 0), axis=1)
        out = {}
        for name in names:
            if name == "count":
                out[name] = cnt
            elif name in ("sum", "mean"):
                psum = part["sum"]
                rsum = jnp.sum(jnp.where(rows, psum[None, :],
                                         jnp.zeros((), psum.dtype)), axis=1)
                out[name] = rsum if name == "sum" else (
                    rsum.astype(jnp.float32)
                    / jnp.maximum(cnt, 1).astype(jnp.float32))
            else:
                fill = hi if name == "min" else lo_sent
                red = jnp.min if name == "min" else jnp.max
                v = red(jnp.where(rows, part[name][None, :], fill), axis=1)
                out[name] = jnp.where(cnt > 0, v, jnp.zeros(
                    (), key_dtype)).astype(key_dtype)
        return out

    return jax.lax.map(chunk, (own_s, ugroups, parts), batch_size=16)


@functools.partial(jax.jit, static_argnames=("ops", "interpret"))
def _engine_median_kernel_exec(groups, keys, ops,
                               *, n_valid=None,
                               interpret: bool | None = None):
    """Grouped median (plus any riding ops) without a window, on Pallas:
    the stream is one pow2-padded frame of the fused SWAG kernel — median
    needs whole groups in one tile, which the tiled groupagg kernel's
    per-tile carry stitching cannot provide."""
    from repro.core.sorter import next_pow2
    from repro.kernels.swag import kernel as _k

    interpret = _common.default_interpret(interpret)
    names = (ops,) if isinstance(ops, str) else tuple(ops)
    n = groups.shape[-1]
    groups = groups.astype(jnp.int32)
    if n_valid is not None:
        groups = jnp.where(jnp.arange(n) < n_valid, groups, PAD_GROUP)
    m = next_pow2(n)
    if m != n:
        groups = jnp.concatenate(
            [groups, jnp.full((m - n,), PAD_GROUP, jnp.int32)])
        keys = jnp.concatenate([keys, jnp.zeros((m - n,), keys.dtype)])
    og, ovs, oc = _k.swag_pallas(groups[None, :], keys[None, :], names,
                                 interpret=interpret)
    num = oc[0]
    valid = jnp.arange(n) < num
    og = jnp.where(valid, og[0, :n], PAD_GROUP)
    return og, {name: v[0, :n] for name, v in ovs.items()}, valid, num


def swag_tpu(groups, keys, *, ws: int, wa: int, op="sum",
             interpret: bool | None = None,
             panes: bool | None = None) -> SwagResult:
    """Deprecated: use ``repro.query.Query(ops=(op,), window=Window(ws, wa))``
    + ``execute`` (``backend="pallas"``/``"pallas-panes"``/``"auto"``)."""
    _deprecated("repro.kernels.swag.ops.swag_tpu",
                "Query(ops=(op,), window=Window(ws, wa))")
    from repro import query as _q
    name = _q.canonical_op(op)
    backend = ("pallas-panes"
               if resolve_panes(ws, wa, groups.shape[-1], panes) and wa < ws
               else "pallas")
    q = _q.Query(ops=(op,), window=_q.Window(ws=ws, wa=wa, panes=panes))
    res, _ = _q.execute(q, groups, keys, backend=backend, interpret=interpret)
    return SwagResult(res.groups, res.values[name], res.valid, res.num_groups)
