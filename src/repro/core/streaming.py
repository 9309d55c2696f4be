"""Streaming (multi-batch) driver — the paper's non-blocking pipeline.

The hardware engine never asserts backpressure: batches of ``P`` tuples flow
through every cycle and a group's aggregate is emitted the moment its last
tuple is identified (which requires the one-batch lookahead buffer, step (a)).

Here a batch is an array of ``N`` tuples; :func:`stream_push` is the
multi-op rolling step (one fused engine pass, per-op carries — the
``n'`` state — threaded between calls).  It is the ``path == "stream"``
backend of the unified query API (``repro.query``);
:class:`StreamingAggregator` is the stateful convenience wrapper built on
top of a planned streaming :class:`repro.query.Query`.  Semantics:

  * a group fully contained in past batches is emitted by the push() that
    first proves it closed (i.e. sees a different leading group id);
  * the final, possibly-open group of each batch is withheld (``open_tail``);
  * ``flush()`` closes the stream and emits the last group.

Outputs are padded to ``N + 1`` slots (the +1 holds a carried-over group that
closed at a batch boundary) with a ``valid`` mask — the static-shape analogue
of the PRRA's per-port valid wires.  ``rr_port`` reproduces the round-robin
port rotation across the whole stream.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as _engine
from repro.core import panetable as _panetable
from repro.core import segscan
from repro.core.combiners import Combiner, get_combiner

Array = jax.Array


class StreamResult(NamedTuple):
    groups: Array      # [N+1]
    values: Array      # [N+1]
    valid: Array       # [N+1]
    num_groups: Array  # scalar
    rr_port: Array     # [N+1] round-robin output port (-1 where invalid)
    #: engine telemetry — always carries ``late_dropped`` for event-time
    #: windows (the lateness-contract violation counter lives in the carry
    #: anyway); the full counters dict with ``collect_stats=True``; None
    #: otherwise
    stats: Any = None
    #: event-time windows only: the ``[rows]`` window end of each row (the
    #: other fields are then ``[rows, lanes]``, one closed window a row;
    #: ``TS_MIN`` where a row holds none)
    window_ends: Any = None


def stream_push(groups: Array, keys: Array, carries, combiners, *,
                n_valid: Array | None = None, p_ports: int = 4):
    """One rolling multi-op engine pass over a batch of sorted tuples.

    ``carries`` is a tuple of :class:`segscan.Carry`, aligned with
    ``combiners``; every carry shares the group / nonempty / emitted fields
    (the group structure is op-independent), so the first one drives the
    close-carry decision.  Returns
    ``((groups, {name: values}, valid, num, rr_port), new_carries)`` with
    ``N + 1`` output slots.
    """
    combiners = tuple(c if isinstance(c, Combiner) else get_combiner(c)
                      for c in combiners)
    n = groups.shape[0]
    lead = carries[0]
    emitted_before = lead.emitted

    closes_carry = lead.nonempty & (groups[0].astype(jnp.int32) != lead.group)
    if n_valid is not None:
        closes_carry = closes_carry & (n_valid > 0)
    carried_group = lead.group
    carried_values = {
        c.name: c.finalize(jax.tree.map(jnp.asarray, cr.state))
        for c, cr in zip(combiners, carries)}

    # neutralize the carries before the engine merges them if being closed
    live_carries = tuple(
        segscan.Carry(
            group=jnp.where(closes_carry, jnp.asarray(-1, jnp.int32),
                            cr.group),
            state=cr.state,
            nonempty=cr.nonempty & ~closes_carry,
            emitted=cr.emitted + closes_carry.astype(jnp.int32),
        ) for cr in carries)

    (res_g, res_values, _res_valid, res_num), new_carries = \
        _engine.multi_engine_step(groups, keys, combiners,
                                  carries=live_carries, open_tail=True,
                                  n_valid=n_valid)

    # prepend the carried group's slot; rotate so valid entries stay dense
    # (if the carry slot is unused, shift engine results up by one)
    num = res_num + closes_carry.astype(jnp.int32)
    shift = (~closes_carry).astype(jnp.int32)
    idx = jnp.arange(n + 1)
    src = jnp.clip(idx + shift, 0, n)

    out_groups = jnp.concatenate([
        jnp.where(closes_carry, carried_group, _engine.PAD_GROUP)[None],
        res_g])[src]
    out_values = {}
    for c in combiners:
        cv = carried_values[c.name]
        col = jnp.concatenate([
            jnp.where(closes_carry, cv, jnp.zeros((), cv.dtype))[None],
            res_values[c.name]])
        out_values[c.name] = col[src]
    out_valid = idx < num

    rr = jnp.where(out_valid, (emitted_before + idx) % p_ports, -1)
    return (out_groups, out_values, out_valid, num, rr), new_carries


def stream_push_table(table, carries, combiners, *, first_group,
                      any_real, p_ports: int = 4):
    """The emission half of a *sharded* rolling push: given the batch's
    merged per-group :class:`repro.core.engine.PartialTable` (from the
    cross-shard combine tree of ``repro.distributed.query_exec``), fold in
    the rolling carry, emit every group but the open tail, and roll the
    tail into the new carry.

    Mirrors :func:`stream_push` slot-for-slot — closed-carry prepend slot,
    round-robin ports, carry bookkeeping — so a sharded streaming query is
    bit-identical to the single-device one (for exactly-mergeable ops).

    ``first_group`` is the raw batch's leading group id (drives the
    close-carry decision, exactly as in :func:`stream_push`); ``any_real``
    is False for an all-padding batch (``n_valid == 0``).
    """
    combiners = tuple(c if isinstance(c, Combiner) else get_combiner(c)
                      for c in combiners)
    c_slots = table.groups.shape[0]
    lead = carries[0]
    emitted_before = lead.emitted

    closes_carry = (lead.nonempty & any_real
                    & (first_group.astype(jnp.int32) != lead.group))
    carried_group = lead.group
    carried_values = {
        c.name: c.finalize(jax.tree.map(jnp.asarray, cr.state))
        for c, cr in zip(combiners, carries)}

    # the carry continues into the batch's first group: fold its state into
    # table row 0 (the earlier range on the left)
    applies = lead.nonempty & any_real & ~closes_carry
    num_t = table.num_groups
    idx = jnp.arange(c_slots)
    emit_row = table.valid & (idx < num_t - 1)   # withhold the open tail
    num = jnp.maximum(num_t - 1, 0) + closes_carry.astype(jnp.int32)

    out_values = {}
    new_carries = []
    tail_idx = jnp.maximum(num_t - 1, 0)
    for c, cr in zip(combiners, carries):
        st = table.states[c.name]
        carry_state = jax.tree.map(lambda x: jnp.asarray(x)[None], cr.state)
        merged0 = c.partial_merge(carry_state,
                                  jax.tree.map(lambda x: x[:1], st))
        st = jax.tree.map(
            lambda m, s: jnp.concatenate(
                [jnp.where(applies, m, s[:1]), s[1:]]), merged0, st)

        vals = c.finalize(st)
        row_vals = jnp.where(emit_row, vals, jnp.zeros((), vals.dtype))
        cv = carried_values[c.name]
        col = jnp.concatenate([
            jnp.where(closes_carry, cv, jnp.zeros((), cv.dtype))[None],
            row_vals])
        out_values[c.name] = col

        tail_state = jax.tree.map(lambda s: s[tail_idx], st)
        new_carries.append(segscan.Carry(
            group=jnp.where(any_real, table.groups[tail_idx],
                            cr.group).astype(jnp.int32),
            state=jax.tree.map(
                lambda t, old: jnp.where(any_real, t, jnp.asarray(old)),
                tail_state, jax.tree.map(jnp.asarray, cr.state)),
            nonempty=cr.nonempty | any_real,
            emitted=(emitted_before + num).astype(jnp.int32),
        ))

    # prepend the carried group's slot; rotate so valid entries stay dense
    shift = (~closes_carry).astype(jnp.int32)
    out_idx = jnp.arange(c_slots + 1)
    src = jnp.clip(out_idx + shift, 0, c_slots)
    row_groups = jnp.where(emit_row, table.groups, _engine.PAD_GROUP)
    out_groups = jnp.concatenate([
        jnp.where(closes_carry, carried_group, _engine.PAD_GROUP)[None],
        row_groups])[src]
    out_values = {name: col[src] for name, col in out_values.items()}
    out_valid = out_idx < num

    rr = jnp.where(out_valid, (emitted_before + out_idx) % p_ports, -1)
    return (out_groups, out_values, out_valid, num, rr), tuple(new_carries)


class StreamingAggregator:
    """Stateful wrapper over a planned streaming Query; one jit-compiled
    fused engine pass per ``push``.

    With ``window=repro.query.Window(...)`` the carry threaded between
    pushes *is* a pane store (:mod:`repro.core.panestore`): each ``push``
    ingests the batch and emits one per-group-window evaluation — the
    paper's SWAG-with-groups approximation as a streaming surface
    (``ws_per_group`` per-group sizes, or ``ws`` as every group's default).

    With ``num_shards``/``mesh`` every push runs the two-phase pipeline of
    :mod:`repro.distributed.query_exec`: the batch is cut into per-shard
    slices (``push`` also accepts them pre-cut as a ``[num_shards, L]``
    array), each shard reduces its slice to a partial table, the combine
    tree merges them, and the rolling carry folds in at emit time —
    bit-identical slots to the single-device aggregator.

    ``collect_stats=True`` threads an :mod:`repro.obs.counters` dict
    through the carry and surfaces it (cumulative over the stream's
    lifetime) as ``StreamResult.stats`` on every push; the default traces
    exactly the pre-observability computation.
    """

    def __init__(self, op="sum", *, window=None, key_dtype=jnp.int32,
                 p_ports: int = 4, num_shards: int | None = None,
                 mesh=None, collect_stats: bool = False):
        from repro import query as _q
        self.combiner = op if isinstance(op, Combiner) else get_combiner(op)
        self.window = window
        if mesh is not None:
            from repro.distributed import query_exec as _qx
            mesh_shards = _qx.mesh_num_shards(mesh)
            if num_shards is not None and num_shards != mesh_shards:
                raise ValueError(
                    f"num_shards={num_shards} contradicts the mesh's "
                    f"{mesh_shards} devices")
            num_shards = mesh_shards
        self.num_shards = num_shards or 1
        self.mesh = mesh
        self.collect_stats = bool(collect_stats)
        self.key_dtype = key_dtype
        self.plan = _q.plan(
            _q.Query(ops=(self.combiner,), window=window, streaming=True),
            backend="reference", num_shards=self.num_shards)
        self.carry = _q.init_stream_state(self.plan, key_dtype,
                                          collect_stats=self.collect_stats)
        self.p_ports = p_ports
        # donate the carry (arg 2): the pane-store ring buffers / rolling
        # carries update in place instead of being copied every push —
        # safe because push() immediately rebinds self.carry to the step's
        # output and nothing else aliases the old buffers
        self._step = jax.jit(_q.stream_fn(self.plan, p_ports=p_ports,
                                          mesh=mesh,
                                          collect_stats=self.collect_stats),
                             donate_argnums=(2,))
        self._carry_leaves = len(jax.tree_util.tree_leaves(self.carry))
        self._donated_buffers = 0

    def _base_carry(self):
        """The engine state, unwrapped from the (state, counters) pair the
        stats-collecting carry threads."""
        return self.carry[0] if self.collect_stats else self.carry

    def _stats(self):
        """The stats to surface on a result: the cumulative counters dict
        when collecting; for event-time windows always at least the
        late-drop counter (it lives in the carry — reading it is free)."""
        if self.collect_stats:
            stats = dict(self.carry[1])
            # host-side gauge (not in the jit carry — the donation machinery
            # is what it measures, so it must not change the carry pytree):
            # carry buffers reused in place instead of copied, cumulative
            stats["store_donated_buffers"] = jnp.asarray(
                self._donated_buffers, jnp.int32)
            return stats
        if self.window is not None and self.window.is_time:
            carry = self._base_carry()
            if isinstance(carry, _panetable.TableState):
                return {"late_dropped": carry.dropped}
            dropped = (jnp.sum(carry[0].dropped) if self.num_shards > 1
                       else carry[0].dropped)
            return {"late_dropped": dropped}
        return None

    def _flush_windows(self, carry):
        """Every window an event-time carry still holds, as one
        :class:`repro.core.panetable.Emission`."""
        ops = (self.combiner,)
        if isinstance(carry, _panetable.TableState):
            return _panetable.flush(self.window.table_spec(), carry, ops)
        from repro.core import eventtime as _eventtime
        from repro.core import panestore as _ps
        rspec = self.window.reorder_spec()
        spec = self.window.store_spec()
        rows = self.window.table_spec().rows
        rstate, pstate, next_end = carry
        if self.num_shards > 1:
            from repro.distributed import query_exec as _qx
            emits, rstate = jax.vmap(
                lambda st: _eventtime.reorder_flush(rspec, st))(rstate)
            eg, ek, ets, elive = _qx.merge_emissions(emits)
        else:
            emit, rstate = _eventtime.reorder_flush(rspec, rstate)
            eg, ek, ets, elive = emit.groups, emit.keys, emit.ts, emit.live
        pstate = _ps.push_time(spec, pstate, eg, ek, ets, live=elive)
        end = jnp.asarray(_panetable.TS_END, jnp.int32)

        def emit_once(cursor):
            out, cursor = _ps.emit_time_windows(spec, pstate, ops, cursor,
                                                end, rows)
            return _panetable.Emission(*out), cursor

        return _panetable.drain(emit_once, next_end, rows)

    def push(self, groups: Array, keys: Array,
             n_valid: Array | None = None,
             timestamps: Array | None = None) -> StreamResult:
        groups = jnp.asarray(groups, jnp.int32)
        keys = jnp.asarray(keys)
        is_time = self.window is not None and self.window.is_time
        if is_time and timestamps is None:
            raise ValueError("event-time windows (Window(range=...)) need "
                             "timestamps= on every push")
        if not is_time and timestamps is not None:
            raise ValueError("timestamps apply to event-time windows "
                             "(Window(range=...)) only")
        if groups.ndim == 2:
            # per-shard pushes: [num_shards, L] slices of one batch
            if groups.shape[0] != self.num_shards:
                raise ValueError(
                    f"per-shard push has {groups.shape[0]} slices but the "
                    f"aggregator shards {self.num_shards} ways")
            groups = groups.reshape(-1)
            keys = keys.reshape(-1)
            if timestamps is not None:
                timestamps = jnp.asarray(timestamps).reshape(-1)
        if is_time:
            (g, values, valid, num, rr, ends), self.carry = self._step(
                groups, keys, self.carry, n_valid, timestamps)
            self._donated_buffers += self._carry_leaves
            return StreamResult(g, values[self.combiner.name], valid, num,
                                rr, self._stats(), ends)
        else:
            (g, values, valid, num, rr), self.carry = self._step(
                groups, keys, self.carry, n_valid)
        self._donated_buffers += self._carry_leaves
        return StreamResult(g, values[self.combiner.name], valid, num, rr,
                            self._stats())

    def flush(self) -> StreamResult:
        """Close the stream: emit the open group (windowed: re-emit every
        live group's current window; event-time: every window left that
        holds a tuple, as closed-window rows, after draining the reorder
        buffer(s)), reset the carry."""
        from repro import query as _q
        carry = self._base_carry()
        stats = self._stats()
        if self.window is not None and self.window.is_time:
            g, values, valid, num, rr, ends = _q.time_ports(
                self._flush_windows(carry), self.p_ports)
            self.carry = _q.init_stream_state(
                self.plan, self.key_dtype, collect_stats=self.collect_stats)
            return StreamResult(g, values[self.combiner.name], valid, num,
                                rr, stats, ends)
        if self.window is not None:
            from repro.core import panestore as _ps
            spec = self.window.store_spec()
            g, values, valid, num = _ps.replay(
                spec, carry, (self.combiner,))
            rr = jnp.where(valid, jnp.arange(spec.capacity) % self.p_ports,
                           -1)
            self.carry = _q.init_stream_state(
                self.plan, carry.keys.dtype,
                collect_stats=self.collect_stats)
            return StreamResult(g, values[self.combiner.name], valid, num,
                                rr, stats)
        (c,) = carry
        value = self.combiner.finalize(jax.tree.map(jnp.asarray, c.state))
        groups = jnp.where(c.nonempty, c.group, _engine.PAD_GROUP)[None]
        values = jnp.where(c.nonempty, value, jnp.zeros((), value.dtype))[None]
        valid = c.nonempty[None]
        num = c.nonempty.astype(jnp.int32)
        rr = jnp.where(valid, c.emitted % self.p_ports, -1)
        self.carry = _q.init_stream_state(
            self.plan,
            jax.tree.leaves(c.state)[0].dtype
            if jax.tree.leaves(c.state) else jnp.int32,
            collect_stats=self.collect_stats)
        return StreamResult(groups, values, valid, num, rr, stats)
