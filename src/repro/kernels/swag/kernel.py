"""Pallas TPU kernels: fused sliding-window aggregation (paper Fig. 4).

Two variants share the in-VMEM engine/median tile code:

* :func:`swag_pallas` — one grid row per window; per window, entirely in VMEM:

      bitonic sort by (group, key)  ->  5-step engine  ->  compacted results

* the **pane** pair :func:`sort_panes_pallas` + :func:`swag_pallas_panes` —
  a prologue pass sorts each WA-sized pane tile *once* (grid over panes),
  then the window pass reads the P = WS/WA presorted panes of window ``i``
  (P overlapping BlockSpecs, rows ``i .. i+P-1``), concatenates them in VMEM
  and *merges* with the bitonic merge network (~log P * log WS sweeps
  instead of the full log^2 WS re-sort) before the same engine/median tail.
  This amortises sorting across the P windows sharing each pane — the
  software rendering of the paper's double-buffered small sorters.

This is the paper's SWAG pipeline collapsed into a single kernel: "offload
the design complexity to small-scale sorting, while benefiting from the
efficiency of the proposed aggregation engine".  Windows are <= 4K tuples in
the paper's target queries — comfortably VMEM-resident.

Median (the paper's non-incremental example) is fused too: after the sort,
the group cardinality is broadcast *backwards* through the run with a
reversed max-segscan (the paper's "append the cardinality alongside the
data"), and the median lane is selected where
``rank == (cardinality - 1) // 2``; compaction then collects exactly one
lane per group.  No hash sets, no worst-case sizing — the paper's pitch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import panestore as _panestore
from repro.core.combiners import get_combiner
from repro.core.engine import PAD_GROUP
from repro.kernels import common


def _row_block(width: int, off: int = 0) -> pl.BlockSpec:
    """Block ``(1, 1, width)`` of a ``[rows, 1, width]`` array at row
    ``i + off``: the row layout Mosaic accepts for one-row tiles (the last
    two block dims equal the array's)."""
    return pl.BlockSpec((1, 1, width), lambda i: (i + off, 0, 0))


def _rows(x):
    """``[rows, width]`` -> the ``[rows, 1, width]`` kernel row layout."""
    return x[:, None, :]


def _median_in_tile(g, k):
    """Lower median per group over one closed, (group,key)-sorted window."""
    sentinel = jnp.iinfo(jnp.int32).min
    starts = g != common.shift_right(g, 1, sentinel)
    ends = g != common.shift_left(g, 1, sentinel)

    count = get_combiner("count")
    ranks = common.tile_segmented_scan(starts, count.lift(k), count)  # 1-based
    card_at_end = jnp.where(ends, ranks, 0)
    # broadcast cardinality backwards: reversed max-segscan seeded at run ends
    card = common.tile_segmented_scan(ends, card_at_end, get_combiner("max"),
                                      reverse=True)

    is_med = (ranks - 1) == ((card - 1) >> 1)
    emit = is_med & (g != PAD_GROUP)
    (cg, cv), _ = common.butterfly_compact(
        emit, (g, k), (PAD_GROUP, jnp.zeros((), k.dtype)))
    return cg, cv


def _multi_tails_in_tile(g, k, combiners: dict):
    """All requested combiner tails over one closed, sorted window.

    ``combiners`` maps op name -> :class:`Combiner` (``"median"`` -> None).
    The segment structure is computed once; every non-median op shares one
    reverse-butterfly compaction pass (``butterfly_compact`` routes the group
    column and all value columns through the same displacement network —
    the hardware's PRRA serving N ``function_select`` units at once).
    Returns ``(cg, {name: cv})``; ``cg`` is PAD_GROUP past the emitted
    groups, so the wrapper counts them.
    """
    sentinel = jnp.iinfo(jnp.int32).min
    starts = g != common.shift_right(g, 1, sentinel)
    ends = g != common.shift_left(g, 1, sentinel)

    vals, fills, names = [], [], []
    for name, comb in combiners.items():
        if comb is None:  # median: separate emit mask, handled below
            continue
        state = comb.lift(k)
        scanned = common.tile_segmented_scan(starts, state, comb)
        vals.append(comb.finalize(scanned))
        fills.append(jnp.zeros((), vals[-1].dtype))
        names.append(name)

    out = {}
    cg = None
    if names:
        emit = ends & (g != PAD_GROUP)
        compacted, _ = common.butterfly_compact(
            emit, (g, *vals), (PAD_GROUP, *fills))
        cg = compacted[0]
        out.update(zip(names, compacted[1:]))
    if None in combiners.values():
        mg, mv = _median_in_tile(g, k)
        med_name = next(n for n, c in combiners.items() if c is None)
        out[med_name] = mv
        if cg is None:
            cg = mg
    return cg, out


def _write_tails(out_refs, cg, vals, combiners):
    og_ref, *ov_refs = out_refs
    og_ref[0] = cg
    for name, ov_ref in zip(combiners, ov_refs):
        ov_ref[0] = vals[name]


def _kernel(g_ref, k_ref, *out_refs, combiners: dict):
    # (window buffer has already framed WS/WA; sort = the paper's small sorter)
    g, k = common.bitonic_sort_tile((g_ref[0], k_ref[0]), num_keys=2)
    _write_tails(out_refs, *_multi_tails_in_tile(g, k, combiners), combiners)


def _out_dtype(op: str, key_dtype):
    if op == "median":
        return key_dtype
    combiner = get_combiner(op)
    return jax.eval_shape(
        lambda x: combiner.finalize(combiner.lift(x)),
        jax.ShapeDtypeStruct((1,), key_dtype)).dtype


def _window_outputs(nw, ws, combiners, key_dtype):
    return ([jax.ShapeDtypeStruct((nw, 1, ws), jnp.int32)]
            + [jax.ShapeDtypeStruct((nw, 1, ws), _out_dtype(name, key_dtype))
               for name in combiners])


def _window_results(outs, combiners):
    """Kernel outputs -> ``(og [NW, WS], {name: ov}, oc [NW])``."""
    og, *ovs = (o[:, 0, :] for o in outs)
    oc = jnp.sum((og != PAD_GROUP).astype(jnp.int32), axis=-1)
    return og, dict(zip(combiners, ovs)), oc


def _sort_panes_kernel(g_ref, k_ref, og_ref, ok_ref):
    g, k = common.bitonic_sort_tile((g_ref[0], k_ref[0]), num_keys=2)
    og_ref[0] = g
    ok_ref[0] = k


def sort_panes_pallas(panes_g, panes_k, *, interpret: bool):
    """Prologue: sort each WA-wide pane once by (group, key).  Takes
    ``[NP, WA]`` panes, returns them sorted in the ``[NP, 1, WA]`` row
    layout :func:`swag_pallas_panes` reads."""
    np_, wa = panes_g.shape
    block = _row_block(wa)
    return pl.pallas_call(
        _sort_panes_kernel,
        grid=(np_,),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1, wa), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1, wa), panes_k.dtype),
        ],
        interpret=interpret,
    )(_rows(panes_g), _rows(panes_k))


def _pane_kernel(*refs, p: int, wa: int, combiners: dict):
    g_refs, k_refs = refs[:p], refs[p:2 * p]
    g = jnp.concatenate([r[0] for r in g_refs], axis=-1)
    k = jnp.concatenate([r[0] for r in k_refs], axis=-1)
    # panes are presorted: merge network instead of a re-sort
    g, k = common.bitonic_merge_tile((g, k), num_keys=2, run=wa)
    _write_tails(refs[2 * p:], *_multi_tails_in_tile(g, k, combiners),
                 combiners)


def _resolve_ops(ops) -> dict:
    """op name(s) -> {name: Combiner | None}; ``None`` marks median."""
    if isinstance(ops, str):
        ops = (ops,)
    return {op: (None if op == "median" else get_combiner(op)) for op in ops}


def swag_pallas_panes(panes_g, panes_k, ops, *, p: int, interpret: bool):
    """Window pass over presorted panes — one merge, N combiner tails.

    ``panes_*``: ``[NP, 1, WA]`` sorted panes (from
    :func:`sort_panes_pallas`); window ``i`` merges pane rows
    ``i .. i+p-1`` — expressed as ``p`` overlapping BlockSpecs over the
    same operand, one per pane offset.  ``ops`` is one op name or a tuple
    of names (the fused multi-op path: the pane framing, the merge network
    and the compaction run once; each extra op adds only its scan + one
    value column).  Returns ``(og, {name: ov}, oc)``.
    """
    np_, _, wa = panes_g.shape
    nw = np_ - p + 1
    ws = p * wa
    combiners = _resolve_ops(ops)

    kern = functools.partial(_pane_kernel, p=p, wa=wa, combiners=combiners)
    pane_specs = [_row_block(wa, off) for off in range(p)]
    outs = pl.pallas_call(
        kern,
        grid=(nw,),
        in_specs=pane_specs + pane_specs,
        out_specs=[_row_block(ws)] * (1 + len(combiners)),
        out_shape=_window_outputs(nw, ws, combiners, panes_k.dtype),
        interpret=interpret,
    )(*([panes_g] * p + [panes_k] * p))
    return _window_results(outs, combiners)


def _direct_tails_in_tile(ck, cnt, names, key_dtype):
    """Every DIRECT_OPS value of one compacted, key-sorted live prefix
    (``cnt`` live lanes) as ``[1, 1]`` columns — the kernel rendering of
    :func:`repro.core.panestore._direct_tails` with ``interpolate=False``."""
    lane = common.lane_iota(ck)
    live = lane < cnt
    nonempty = cnt > 0
    zero = jnp.zeros((), ck.dtype)
    acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype

    def pick(i):
        return jnp.sum(jnp.where(lane == i, ck, zero), axis=-1, keepdims=True)

    def total():
        return jnp.sum(jnp.where(live, ck, zero).astype(acc), axis=-1,
                       keepdims=True)

    out = {}
    for name in names:
        if name == "count":
            out[name] = cnt
        elif name == "sum":
            out[name] = total()
        elif name == "min":
            out[name] = jnp.where(nonempty, pick(0), zero)
        elif name == "max":
            out[name] = jnp.where(nonempty, pick(cnt - 1), zero)
        elif name == "mean":
            out[name] = (total().astype(jnp.float32)
                         / jnp.maximum(cnt, 1).astype(jnp.float32))
        elif name == "median":
            out[name] = jnp.where(nonempty,
                                  pick(jnp.maximum(cnt - 1, 0) >> 1), zero)
        elif name == "distinct_count":
            prev = common.shift_right(ck, 1, _panestore._key_sentinel(
                ck.dtype))
            neq = (ck != prev) & live
            out[name] = jnp.sum(neq.astype(jnp.int32), axis=-1,
                                keepdims=True)
        else:  # pragma: no cover - guarded by the registry
            raise ValueError(f"{name} is not a direct replay op")
    return out


def _pergroup_kernel(k_ref, v_ref, *ov_refs, names, run):
    """One replay row of the per-group pane store, entirely in VMEM:

        presorted runs  ->  bitonic merge (by key, liveness as payload)
                        ->  ONE shared butterfly compaction
                        ->  N operator tails off the compacted window

    All lanes of a row belong to one group (panes are per-group), so no
    group column rides through the merge — the liveness mask (slot
    occupancy + open-pane fill + staleness, folded upstream by
    ``panestore.gather_runs``) is the only metadata.  Every requested op
    reads the same compacted, key-sorted live prefix: the multi-op sharing
    of the global-window kernels, with the compaction network doing the
    work the PRRA's reverse butterfly does in hardware.
    """
    k, vi = common.bitonic_merge_tile((k_ref[0], v_ref[0]), num_keys=1,
                                      run=run)
    sentinel = _panestore._key_sentinel(k.dtype)
    (ck,), cnt = common.butterfly_compact(vi != 0, (k,), (sentinel,))
    vals = _direct_tails_in_tile(ck, cnt, names, k.dtype)
    for name, ov_ref in zip(names, ov_refs):
        ov_ref[0] = vals[name]


def _pergroup_out_dtype(name: str, key_dtype):
    return jax.eval_shape(
        lambda k, c: _panestore._direct_tails(
            k, c, (name,), key_dtype=key_dtype, interpolate=False)[name],
        jax.ShapeDtypeStruct((8,), key_dtype),
        jax.ShapeDtypeStruct((), jnp.int32)).dtype


def pergroup_replay_pallas(run_keys, run_valid, ops, *, run: int,
                           interpret: bool):
    """Replay pass over gathered per-group pane subsets.

    ``run_keys`` / ``run_valid``: [R, S*WA] — R rows (one per candidate
    group per evaluation), each a concatenation of S key-sorted WA-runs
    with a liveness mask (see :class:`repro.core.panestore.ReplayRuns`).
    ``ops`` is one op name or a tuple of :data:`repro.core.panestore.
    DIRECT_OPS` names.  Returns ``{name: [R] values}``.
    """
    r, L = run_keys.shape
    names = (ops,) if isinstance(ops, str) else tuple(ops)
    kern = functools.partial(_pergroup_kernel, names=names, run=run)
    outs = pl.pallas_call(
        kern,
        grid=(r,),
        in_specs=[_row_block(L)] * 2,
        out_specs=[_row_block(1)] * len(names),
        out_shape=[jax.ShapeDtypeStruct(
            (r, 1, 1), _pergroup_out_dtype(name, run_keys.dtype))
            for name in names],
        interpret=interpret,
    )(_rows(run_keys), _rows(run_valid))
    return {name: o[:, 0, 0] for name, o in zip(names, outs)}


#: per-slot partials the fused per-group kernel emits, by op
_SLOT_PARTIALS = {"count": ("count",), "sum": ("count", "sum"),
                  "mean": ("count", "sum"), "min": ("count", "min"),
                  "max": ("count", "max")}


def _pergroup_fused_kernel(ck_ref, slot_ref, lane_ref, seq_ref, cnt_ref,
                           lo_ref, sm_ref, own_ref, *refs, parts, wa):
    """One WA chunk of the fused push + partial pass: the pane-store ring
    buffers live in VMEM scratch across the whole sequential grid, so each
    chunk is ONE dispatch — scalar writes into the resident store, the
    close-sort epilogue, then the per-slot partial aggregates — with no
    store round trip through HBM between update and evaluation.

    The store is held transposed, ``[WA, C]``: slots on lanes, a slot's
    tuples down the sublanes.  Per-slot partials are then sublane
    reductions that land directly in the ``[1, C]`` row layout of the
    directory snapshots, and a tuple write is one dynamic-row
    read-modify-write with a lane mask.  The tuple coordinates arrive as
    SMEM scalars.

    The *placement* decisions (slot/lane/seq per tuple, close/retire/evict
    fallout as directory snapshots) arrive precomputed by the XLA
    directory scan of :func:`repro.core.swag.pergroup_write_plan`; the
    per-group combine of the slot partials runs in XLA after the kernel
    (:func:`repro.kernels.swag.ops._combine_slot_partials`).

    The close-sort runs lexicographically on ``(key, seq)``: lanes of a
    closing pane hold strictly increasing seqs in arrival order, so the
    2-key bitonic sort *is* the store's stable-by-key argsort.
    """
    out_refs = refs[:len(parts)]
    kk_s, ss_s = refs[len(parts):]
    c = kk_s.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        kk_s[...] = jnp.zeros(kk_s.shape, kk_s.dtype)
        ss_s[...] = jnp.zeros(ss_s.shape, jnp.int32)

    slot_lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def write(t, carry):
        row = pl.ds(lane_ref[0, 0, t], 1)
        hit = slot_lane == slot_ref[0, 0, t]
        kk_s[row, :] = jnp.where(hit, ck_ref[0, 0, t], kk_s[row, :])
        ss_s[row, :] = jnp.where(hit, seq_ref[0, 0, t], ss_s[row, :])
        return carry

    jax.lax.fori_loop(0, wa, write, 0)

    kk = kk_s[...]
    ss = ss_s[...]
    sk, sq = common.bitonic_sort_tile((kk, ss), num_keys=2, axis=0)
    closing = sm_ref[0] != 0
    kk = jnp.where(closing, sk, kk)
    ss = jnp.where(closing, sq, ss)
    kk_s[...] = kk
    ss_s[...] = ss

    lanes = jax.lax.broadcasted_iota(jnp.int32, kk.shape, 0)
    live = ((own_ref[0] != PAD_GROUP) & (lanes < cnt_ref[0])
            & (ss >= lo_ref[0]))
    key_dtype = kk.dtype
    row = pl.ds(pl.program_id(0) % 8, 1)
    for part, o_ref in zip(parts, out_refs):
        if part == "count":
            v = jnp.sum(live.astype(jnp.int32), axis=0, keepdims=True)
        elif part == "sum":
            acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
            v = jnp.sum(jnp.where(live, kk, 0).astype(acc), axis=0,
                        keepdims=True)
        elif part == "min":
            v = jnp.min(jnp.where(live, kk, _panestore._key_sentinel(
                key_dtype)), axis=0, keepdims=True)
        else:  # max
            v = jnp.max(jnp.where(live, kk, _min_sentinel(key_dtype)),
                        axis=0, keepdims=True)
        o_ref[row, :] = v


def _min_sentinel(dtype):
    return (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
            else -jnp.inf)


def pergroup_slot_partials_pallas(chunk_keys, slots, lanes, seqs, own_s,
                                  cnt_s, lo_s, sortmask, ops, *, interpret):
    """Fused push + per-slot partials over per-group pane chunks: the ring
    buffers stay VMEM-resident across the sequential ``grid=(NE,)``
    (Pallas scratch persists between grid steps), so the historical
    per-chunk update-store -> gather -> replay HBM round trip collapses
    into one launch for the whole stream.

    Inputs are :func:`repro.core.swag.pergroup_write_plan` outputs
    (``chunk_keys/slots/lanes/seqs`` ``[NE, WA]``; directory snapshots
    ``[NE, C]``); ``ops`` are partial-path names.  Returns
    ``{part: [NE, C]}`` per-slot partials for ``part`` in ``count``,
    ``sum``, ``min``, ``max`` as the ops need them (eight chunks' rows per
    output block, so the HBM writes are whole tiles).
    """
    ne, wa = chunk_keys.shape
    c = own_s.shape[1]
    names = (ops,) if isinstance(ops, str) else tuple(ops)
    parts = tuple(dict.fromkeys(p for nm in names
                                for p in _SLOT_PARTIALS[nm]))
    key_dtype = chunk_keys.dtype
    acc = get_combiner("sum").lift(jnp.zeros((), key_dtype)).dtype
    dtypes = {"count": jnp.int32, "sum": acc, "min": key_dtype,
              "max": key_dtype}
    ne8 = -(-ne // 8) * 8

    kern = functools.partial(_pergroup_fused_kernel, parts=parts, wa=wa)
    sblock = pl.BlockSpec((1, 1, wa), lambda i: (i, 0, 0),
                          memory_space=pltpu.SMEM)
    oblock = pl.BlockSpec((8, c), lambda i: (i // 8, 0))
    outs = pl.pallas_call(
        kern,
        grid=(ne,),
        in_specs=[sblock] * 4 + [_row_block(c)] * 4,
        out_specs=[oblock] * len(parts),
        out_shape=[jax.ShapeDtypeStruct((ne8, c), dtypes[p]) for p in parts],
        scratch_shapes=[pltpu.VMEM((wa, c), key_dtype),
                        pltpu.VMEM((wa, c), jnp.int32)],
        interpret=interpret,
    )(_rows(chunk_keys), _rows(slots.astype(jnp.int32)),
      _rows(lanes.astype(jnp.int32)), _rows(seqs.astype(jnp.int32)),
      _rows(cnt_s), _rows(lo_s), _rows(sortmask.astype(jnp.int32)),
      _rows(own_s))
    return {p: o[:ne] for p, o in zip(parts, outs)}


def _twostack_kernel(kf_ref, vf_ref, kb_ref, vb_ref, *out_refs, names):
    """The stack-flip step of the flip-batched two-stack SWAG, one epoch per
    grid row: an inclusive suffix scan over the epoch's front region and an
    inclusive prefix scan over its back region (masked lanes pinned to each
    op's identity) — the flip of Tangwongsan et al.'s two-stack algorithm
    as log2(wcap) Hillis–Steele sweeps in VMEM.  The scan body is the
    *same* code the reference strategy runs batched
    (:func:`repro.core.twostack.flip_scans`), with the kernel's roll-based
    shifts."""
    from repro.core import twostack as _twostack

    kf, vf = kf_ref[0], vf_ref[0] != 0
    kb, vb = kb_ref[0], vb_ref[0] != 0
    scans = _twostack.flip_scans(kf, vf, kb, vb, names, kf.dtype,
                                 shift_left=common.shift_left,
                                 shift_right=common.shift_right)
    for i, name in enumerate(names):
        fsuf, bpre = scans[name]
        out_refs[2 * i][0] = fsuf
        out_refs[2 * i + 1][0] = bpre


def _state_dtype(name: str, key_dtype):
    comb = get_combiner(name)
    return jax.eval_shape(lambda x: comb.lift(x),
                          jax.ShapeDtypeStruct((1,), key_dtype)).dtype


def twostack_flip_pallas(kf, vf, kb, vb, names, *, interpret: bool):
    """Batched flip over ``[NE, wcap]`` epoch regions (see
    :mod:`repro.core.twostack`).  ``kf``/``kb`` are the front/back key
    slices, ``vf``/``vb`` their liveness masks.  Returns
    ``{name: (front_suffix, back_prefix)}``, each ``[NE, wcap]``."""
    ne, wcap = kf.shape
    names = tuple(names)
    kern = functools.partial(_twostack_kernel, names=names)
    block = _row_block(wcap)
    out_shape = []
    for name in names:
        dt = _state_dtype(name, kf.dtype)
        out_shape += [jax.ShapeDtypeStruct((ne, 1, wcap), dt)] * 2
    outs = pl.pallas_call(
        kern,
        grid=(ne,),
        in_specs=[block] * 4,
        out_specs=[block] * (2 * len(names)),
        out_shape=out_shape,
        interpret=interpret,
    )(_rows(kf), _rows(vf.astype(jnp.int32)), _rows(kb),
      _rows(vb.astype(jnp.int32)))
    return {name: (outs[2 * i][:, 0, :], outs[2 * i + 1][:, 0, :])
            for i, name in enumerate(names)}


def swag_pallas(frames_g, frames_k, ops, *, interpret: bool):
    """frames_*: [NW, WS] framed windows, WS a power of two.  ``ops`` is one
    op name or a tuple (fused multi-op: one sort, N tails).  Returns
    ``(og, {name: ov}, oc)``."""
    nw, ws = frames_g.shape
    combiners = _resolve_ops(ops)

    kern = functools.partial(_kernel, combiners=combiners)
    block = _row_block(ws)
    outs = pl.pallas_call(
        kern,
        grid=(nw,),
        in_specs=[block, block],
        out_specs=[block] * (1 + len(combiners)),
        out_shape=_window_outputs(nw, ws, combiners, frames_k.dtype),
        interpret=interpret,
    )(_rows(frames_g), _rows(frames_k))
    return _window_results(outs, combiners)
