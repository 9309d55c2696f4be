"""jit-safe device-side engine counters.

A counters value is a plain ``dict[str, jax.Array]`` — a pytree that
threads cleanly through ``lax.scan`` carries, ``jax.vmap``, ``jax.jit``
boundaries and ``NamedTuple`` stream states.  Every helper below is
``None``-transparent: counter sites take ``counters=None`` by default and
branch at *trace time*, so the off path emits zero extra ops and traces
the identical jaxpr as code that never heard of counters.

Conventions
-----------
- values are scalar ``int32``/``float32`` arrays (or small 1-D arrays for
  per-round sequences such as the shard combine tree);
- helpers are functional — they return a new dict, never mutate;
- ``ensure`` is called once *before* a scan so the carry pytree structure
  is stable across iterations.

Counter names used by the engine:

=========================  ====================================================
``pane_evictions``         occupied pane slots (pane-partial table: rows)
                           displaced by capacity pressure
``pane_occupancy_hwm``     high-water mark of occupied slots in the pane store
``pane_dir_fallbacks``     pushes whose write plan took the per-tuple directory
                           scan because their panes did not fit the store
``reorder_depth_hwm``      high-water mark of buffered tuples in the reorder ring
``reorder_forced_pops``    pops forced by a full ring rather than the watermark
``late_dropped``           tuples dropped for violating the lateness contract
``watermark``              current (min-merged) event-time watermark
``windows_emitted``        closed event-time windows emitted so far
``pane_rows_hwm``          high-water mark of rows in the pane-partial table
``table_merge_wide``       pushes whose rows did not fit the pane-partial
                           table's pad tail (its merge took twice the lanes)
``watermark_lag``          max shard watermark minus the merged global watermark
``stream_tuples``          tuples pushed through a streaming carry
``stream_emitted``         groups emitted (retired) by streaming pushes
``combine_rounds``         rounds in the shard combine tree (static)
``combine_round_width``    partial-table row width after each round (static)
``combine_round_groups``   live groups summed over nodes after each round
``combine_round_bytes``    bytes of partial-table state merged in each round
=========================  ====================================================
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

Counters = dict  # dict[str, jax.Array]


def init(**values) -> Counters:
    """Fresh counters dict; values coerced to int32 scalars unless given."""
    out = {}
    for name, v in values.items():
        out[name] = jnp.asarray(v)
    return out


def ensure(counters: Optional[Counters], names: tuple,
           dtype=jnp.int32) -> Optional[Counters]:
    """Zero-init any missing ``names`` so a scan carry has stable structure."""
    if counters is None:
        return None
    out = dict(counters)
    for name in names:
        if name not in out:
            out[name] = jnp.zeros((), dtype)
    return out


def bump(counters: Optional[Counters], name: str, amount) -> Optional[Counters]:
    """Add ``amount`` to ``counters[name]`` (zero-init if absent)."""
    if counters is None:
        return None
    out = dict(counters)
    amount = jnp.asarray(amount)
    prev = out.get(name)
    out[name] = amount if prev is None else prev + amount
    return out


def high_water(counters: Optional[Counters], name: str, value) -> Optional[Counters]:
    """Raise ``counters[name]`` to ``value`` if larger."""
    if counters is None:
        return None
    out = dict(counters)
    value = jnp.asarray(value)
    prev = out.get(name)
    out[name] = value if prev is None else jnp.maximum(prev, value)
    return out


def put(counters: Optional[Counters], name: str, value) -> Optional[Counters]:
    """Overwrite ``counters[name]`` with ``value`` (gauge semantics)."""
    if counters is None:
        return None
    out = dict(counters)
    out[name] = jnp.asarray(value)
    return out
