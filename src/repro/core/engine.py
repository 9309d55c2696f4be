"""The group-by-aggregate engine — the paper's Fig. 2, five steps, in JAX.

    (a) buffer one batch  ->  handled by the streaming driver / ``open_tail``
    (b) mark last-of-group (entities t)          ->  :func:`segscan.segment_ends`
    (c) rolling segmented prefix scan (entities n) -> :func:`segscan.segmented_scan`
    (d) finalize + rolling carry (entities n')   ->  ``combiner.finalize`` + Carry
    (e) reverse-butterfly round-robin compaction ->  prefix-sum of valid bits
                                                     + one static-shape scatter

Static shapes (XLA) replace the hardware's valid wires: outputs are padded to
the input length with a ``valid`` mask and a ``num_groups`` count.  The PRRA's
*round-robin* port rotation is preserved as :func:`rr_ports` (rolling offset =
groups emitted so far), which the streaming driver threads through batches.

Inputs must be sorted by group id (the engine's contract, as in the paper —
an upstream sorter provides this; see ``core/sorter.py``).
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import segscan
from repro.core.combiners import Combiner, get_combiner, partial_combiner

Array = jax.Array

#: sentinel group id for padding slots (sorts after every real group id)
PAD_GROUP = jnp.iinfo(jnp.int32).max


class GroupAggResult(NamedTuple):
    groups: Array       # [N] int32   — compacted unique group ids (padded tail)
    values: Array       # [N]         — aggregate per group (padded tail)
    valid: Array        # [N] bool    — which output slots hold a real group
    num_groups: Array   # scalar int32


class PartialTable(NamedTuple):
    """A compact per-group *partial result table* — the engine stopped one
    step before ``finalize``.

    This is the unit of two-phase (mergeable-state) execution: each shard /
    pane reduces its range of the stream to one of these, and tables merge
    with :func:`combine_partial_tables` until one remains, which then
    finalizes.  Rows are ascending unique group ids with a ``PAD_GROUP``
    tail; invalid rows hold the combiner identity.
    """
    groups: Array       # [C] int32 — ascending unique group ids (PAD tail)
    states: dict        # {op name: state pytree, each leaf [C, ...]}
    valid: Array        # [C] bool
    num_groups: Array   # scalar int32


def _resolve(op) -> Combiner:
    return op if isinstance(op, Combiner) else get_combiner(op)


def _compact_layout(groups: Array, emit: Array):
    """Step (e), shared by every emitting pass: the reverse-butterfly
    compaction permutation (prefix sum of ``emit``), the compacted group
    column, and the valid mask/count."""
    n = groups.shape[0]
    perm = segscan.exclusive_prefix_sum(emit)
    scatter_idx = jnp.where(emit, perm, n)  # invalid -> dropped slot
    out_groups = jnp.full((n + 1,), PAD_GROUP, jnp.int32).at[scatter_idx].set(
        groups, mode="drop")[:n]
    num = jnp.sum(emit.astype(jnp.int32))
    out_valid = jnp.arange(n) < num
    return scatter_idx, out_groups, num, out_valid


def _scatter_states(scanned, ident, scatter_idx, n: int):
    """Compact a scanned state pytree: each leaf scattered by the shared
    permutation, dropped slots filled with the combiner identity leaf."""
    def one(leaf, fill):
        buf = jnp.full((n + 1,) + leaf.shape[1:], fill, leaf.dtype)
        return buf.at[scatter_idx].set(leaf, mode="drop")[:n]

    return jax.tree.map(one, scanned, jax.tree.map(jnp.asarray, ident))


def multi_engine_step(groups: Array, keys: Array, ops, *,
                      carries=None, open_tail: bool = False,
                      n_valid: Array | None = None):
    """One fused engine pass evaluating several combiners over one stream.

    The segment structure (entities ``t``: start/end marks, the compaction
    permutation, the valid count) is computed **once**; each combiner adds
    only its own lift + segmented scan + finalize + value scatter — the
    software rendering of the paper's ``function_select``: one scan topology,
    N concurrently-selected functional units.

    Args:
      groups: [N] int group ids, sorted ascending (ties contiguous).
      keys:   [N] values to aggregate.
      ops:    tuple of combiner names / :class:`Combiner` objects.
      carries: optional tuple of rolling :class:`segscan.Carry` states,
        aligned with ``ops`` (streaming mode); ``None`` entries initialise.
      open_tail: if True, the final group is *not* emitted — it may continue
        into the next batch (paper step (a): the one-batch lookahead buffer).
      n_valid: optional scalar — only the first ``n_valid`` tuples are real
        (the "dense stream" requirement; padding must sit at the tail).

    Returns:
      ``((out_groups, values, out_valid, num), new_carries)`` where ``values``
      maps each combiner's name to its [N] value column (all columns share
      ``out_groups``/``out_valid``/``num``) and ``new_carries`` is a tuple
      aligned with ``ops``.
    """
    combiners = tuple(_resolve(op) for op in ops)
    names = [c.name for c in combiners]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate combiner names in ops: {names}")

    n = groups.shape[0]
    groups = groups.astype(jnp.int32)

    if n_valid is not None:
        in_valid = jnp.arange(n) < n_valid
        groups = jnp.where(in_valid, groups, PAD_GROUP)
    else:
        in_valid = None

    # (b) entities t: mark last tuple per group — shared across all ops
    ends = segscan.segment_ends(groups)
    starts = segscan.segment_starts(groups)

    if carries is None:
        carries = (None,) * len(combiners)
    carries = tuple(
        segscan.init_carry(c, keys.dtype) if cr is None else cr
        for c, cr in zip(combiners, carries))

    # (c)+(d) entities n / n': per-op scan + rolling carry merge
    scanneds = []
    for combiner, carry in zip(combiners, carries):
        state = combiner.lift(keys)
        scanned = segscan.segmented_scan(starts, state, combiner)
        scanned = segscan.merge_carry(carry, groups, scanned, combiner)
        scanneds.append(scanned)

    emit = ends
    if in_valid is not None:
        emit = emit & (groups != PAD_GROUP)
    if open_tail:
        # the batch's final *real* tuple is withheld (its group may continue)
        last_real = (jnp.cumsum(emit[::-1].astype(jnp.int32))[::-1] == 1) & emit
        emit = emit & ~last_real

    # (e) reverse butterfly: permutation index = prefix sum of valid bits —
    # computed once, reused by every op's value scatter
    scatter_idx, out_groups, num, out_valid = _compact_layout(groups, emit)

    values = {}
    new_carries = []
    for combiner, carry, scanned in zip(combiners, carries, scanneds):
        vals = combiner.finalize(scanned)
        values[combiner.name] = jnp.zeros(
            (n + 1,) + vals.shape[1:], vals.dtype).at[
            scatter_idx].set(vals, mode="drop")[:n]

        new_carry = segscan.update_carry(carry, groups, scanned, emit, combiner)
        if in_valid is not None:
            # an all-padding batch must not clobber the carry group id
            any_real = jnp.any(in_valid)
            tail_idx = jnp.maximum(jnp.sum(in_valid.astype(jnp.int32)) - 1, 0)
            tail_state = jax.tree.map(lambda s: s[tail_idx], scanned)
            new_carry = segscan.Carry(
                group=jnp.where(any_real, groups[tail_idx],
                                carry.group).astype(jnp.int32),
                state=jax.tree.map(
                    lambda t, c: jnp.where(any_real, t, c), tail_state,
                    jax.tree.map(jnp.asarray, carry.state)),
                nonempty=carry.nonempty | any_real,
                emitted=(carry.emitted + num).astype(jnp.int32),
            )
        new_carries.append(new_carry)

    return (out_groups, values, out_valid, num), tuple(new_carries)


def multi_engine_partials(groups: Array, keys: Array, ops, *,
                          n_valid: Array | None = None) -> PartialTable:
    """The local phase of two-phase execution: one engine pass that stops
    **before** ``finalize`` and returns the compact per-group partial-state
    table of this range of the stream.

    Same contract as :func:`multi_engine_step` (input sorted by group id;
    ``n_valid`` marks a real prefix) but no carries and no finalization —
    the caller merges tables from adjacent ranges with
    :func:`combine_partial_tables` and finalizes once, which is exactly the
    paper's split into per-range entities ``n`` and combining entities
    ``n'``.
    """
    combiners = tuple(_resolve(op) for op in ops)
    names = [c.name for c in combiners]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate combiner names in ops: {names}")

    n = groups.shape[0]
    groups = groups.astype(jnp.int32)
    if n_valid is not None:
        groups = jnp.where(jnp.arange(n) < n_valid, groups, PAD_GROUP)

    starts = segscan.segment_starts(groups)
    emit = segscan.segment_ends(groups) & (groups != PAD_GROUP)
    scatter_idx, out_groups, num, out_valid = _compact_layout(groups, emit)

    states = {}
    for combiner in combiners:
        scanned = segscan.segmented_scan(starts, combiner.lift(keys), combiner)
        states[combiner.name] = _scatter_states(
            scanned, combiner.identity((), keys.dtype), scatter_idx, n)
    return PartialTable(out_groups, states, out_valid, num)


def combine_partial_tables(a: PartialTable, b: PartialTable, ops, *,
                           key_dtype) -> PartialTable:
    """Merge two per-range partial tables (``a`` the earlier range) — one
    node of the cross-device combine tree.

    Both tables' rows are ascending group ids with ``PAD_GROUP`` tails, so
    their rows interleave by a stable two-run merge: each row's output
    position is its own index plus its rank in the other table (``a`` first
    within a group, which the order-sensitive merges — dc's boundary rule,
    first/last — require).  That makes equal groups adjacent without a
    general sort; a segmented fold with each op's
    :func:`repro.core.combiners.partial_combiner` then collapses them and
    the shared compaction re-packs the result.  Output width is the sum of
    the input widths (static shapes; real groups can never exceed that).
    """
    combiners = tuple(_resolve(op) for op in ops)
    ga, gb = a.groups.astype(jnp.int32), b.groups.astype(jnp.int32)
    na, nb = ga.shape[0], gb.shape[0]
    pos_a = jnp.arange(na) + jnp.searchsorted(gb, ga, side="left")
    pos_b = jnp.arange(nb) + jnp.searchsorted(ga, gb, side="right")

    def merge(x, y):
        out = jnp.zeros((na + nb,) + x.shape[1:], x.dtype)
        out = out.at[pos_a].set(x, unique_indices=True)
        return out.at[pos_b].set(y, unique_indices=True)

    g = merge(ga, gb)
    states = {c.name: jax.tree.map(merge, a.states[c.name], b.states[c.name])
              for c in combiners}

    n = g.shape[0]
    starts = segscan.segment_starts(g)
    emit = segscan.segment_ends(g) & (g != PAD_GROUP)
    scatter_idx, out_groups, num, out_valid = _compact_layout(g, emit)

    out_states = {}
    for combiner in combiners:
        folded = segscan.segmented_scan(starts, states[combiner.name],
                                        partial_combiner(combiner))
        out_states[combiner.name] = _scatter_states(
            folded, combiner.identity((), key_dtype), scatter_idx, n)
    return PartialTable(out_groups, out_states, out_valid, num)


def empty_partial_table(width: int, ops, key_dtype) -> PartialTable:
    """The identity of :func:`combine_partial_tables` — what an empty shard
    contributes to the combine tree."""
    combiners = tuple(_resolve(op) for op in ops)
    states = {
        c.name: jax.tree.map(
            lambda fill: jnp.full((width,) + jnp.shape(fill),
                                  jnp.asarray(fill), jnp.asarray(fill).dtype),
            c.identity((), key_dtype))
        for c in combiners}
    return PartialTable(
        groups=jnp.full((width,), PAD_GROUP, jnp.int32),
        states=states,
        valid=jnp.zeros((width,), bool),
        num_groups=jnp.zeros((), jnp.int32),
    )


def finalize_partial_table(table: PartialTable, ops) -> tuple[Array, dict,
                                                              Array, Array]:
    """The last stage of the two-phase pipeline: apply each op's
    ``finalize`` to the merged table (invalid rows zeroed)."""
    combiners = tuple(_resolve(op) for op in ops)
    values = {}
    for combiner in combiners:
        v = combiner.finalize(table.states[combiner.name])
        values[combiner.name] = jnp.where(table.valid, v,
                                          jnp.zeros((), v.dtype))
    return table.groups, values, table.valid, table.num_groups


def engine_step(groups: Array, keys: Array, op, *,
                carry: segscan.Carry | None = None,
                open_tail: bool = False,
                n_valid: Array | None = None) -> tuple[GroupAggResult, segscan.Carry]:
    """One pass of the engine over a batch of sorted ``(group, key)`` tuples.

    Single-op case of :func:`multi_engine_step`; see there for argument
    semantics.  Returns ``(result, new_carry)``.
    """
    combiner = _resolve(op)
    (g, values, valid, num), (new_carry,) = multi_engine_step(
        groups, keys, (combiner,), carries=(carry,), open_tail=open_tail,
        n_valid=n_valid)
    return GroupAggResult(g, values[combiner.name], valid, num), new_carry


def _group_by_aggregate(groups: Array, keys: Array, op="sum", *,
                        n_valid: Array | None = None) -> GroupAggResult:
    """Internal (non-deprecated) single-shot group-by-aggregate.

    The SQL ``SELECT g, f(k) FROM t GROUP BY g ORDER BY g`` of the paper's
    Algorithm 1 (order comes free: input is sorted, compaction is stable).
    Library code calls this; external callers use :class:`repro.query.Query`.
    """
    result, _ = engine_step(groups, keys, op, carry=None, open_tail=False,
                            n_valid=n_valid)
    return result


def _deprecated(old: str, hint: str) -> None:
    """One shared deprecation funnel for every legacy entry-point shim."""
    warnings.warn(
        f"{old} is deprecated; build a repro.query.Query ({hint}) and call "
        f"repro.query.execute instead",
        DeprecationWarning, stacklevel=3)


def group_by_aggregate(groups: Array, keys: Array, op="sum", *,
                       n_valid: Array | None = None) -> GroupAggResult:
    """Deprecated: use ``repro.query.Query(ops=(op,))`` + ``execute``."""
    _deprecated("repro.core.group_by_aggregate", "Query(ops=(op,))")
    from repro import query as _q
    name = op.name if isinstance(op, Combiner) else _q.canonical_op(op)
    res, _ = _q.execute(_q.Query(ops=(op,)), groups, keys, n_valid=n_valid,
                        backend="reference")
    return GroupAggResult(res.groups, res.values[name], res.valid,
                          res.num_groups)


def multi_aggregate(groups: Array, keys: Array, ops: tuple[str, ...],
                    *, n_valid: Array | None = None) -> dict[str, GroupAggResult]:
    """Deprecated: use ``repro.query.Query(ops=ops)`` + ``execute`` (which
    additionally fuses the shared mark/compact work across operators)."""
    _deprecated("repro.core.multi_aggregate", "Query(ops=ops)")
    from repro import query as _q
    res, _ = _q.execute(_q.Query(ops=tuple(ops)), groups, keys,
                        n_valid=n_valid, backend="reference")
    return {name: GroupAggResult(res.groups,
                                 res.values[_q.canonical_op(name)],
                                 res.valid, res.num_groups)
            for name in ops}


def rr_ports(result: GroupAggResult, emitted_before: Array, p: int) -> Array:
    """Round-robin output port per emitted group — the PRRA's defining
    property.  ``emitted_before`` is ``carry.emitted`` *prior* to this batch.
    """
    idx = jnp.arange(result.groups.shape[0])
    return jnp.where(result.valid, (emitted_before + idx) % p, -1)
