"""In-tile primitives shared by the Pallas kernels.

These are pure ``jnp`` functions over VMEM-resident 2-D values (rows of a
block, lanes along ``axis``), written in the forms the TPU compiler
(Mosaic) lowers:

  * **lane movement is** ``pltpu.roll`` **plus a lane-iota mask** — the
    bitonic partner ``p ^ j`` is one of two rolls selected by bit ``j`` of
    the lane index, a shift is a roll whose wrapped lanes are masked to a
    fill value, and a run reversal is the XOR-butterfly ``p ^ (run - 1)``
    built from those partner selections.  No gathers, reshapes, flips or
    concatenates;
  * **prefix sums are log-step roll sums** (Hillis–Steele), not
    ``cumsum``;
  * **masks that move between lanes are int32** — booleans are only
    produced by comparisons and consumed by ``jnp.where`` / ``&`` / ``|``;
  * static shapes and static loop bounds only (unrolled at trace time, like
    the fixed wiring of the FPGA design);
  * combiner states are tuples of same-shape arrays (struct-of-arrays).

``pltpu.roll`` only has a lowering inside a Pallas kernel (compiled or
interpret mode), so these primitives run inside kernels only.  The
reference backend keeps its own gather-based network in
:mod:`repro.core.sorter` — the independent oracle the kernels are checked
against.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.combiners import Combiner

Array = jax.Array


def is_cpu(devices=None) -> bool:
    """True when execution lands on CPU (no Mosaic compiler).

    With ``devices`` (e.g. the devices of a mesh a query is being sharded
    over) the probe answers for *those* devices instead of the process
    default — each shard of a multi-device query picks its backend for the
    hardware it actually runs on."""
    if devices is not None:
        devices = list(devices)
        if devices:
            return devices[0].platform == "cpu"
    return jax.default_backend() == "cpu"


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve the shared ``interpret`` tri-state of every kernel wrapper:
    ``None`` auto-selects Pallas interpret mode on CPU (the validation path
    mandated for this container) and compiled Mosaic on TPU.  This is the
    single capability probe behind :mod:`repro.kernels.registry`."""
    return is_cpu() if interpret is None else interpret


def lane_iota(x: Array, axis: int = -1) -> Array:
    """int32 lane index along ``axis``, shaped like ``x``."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, axis % x.ndim)


def lane_roll(x: Array, shift: int, axis: int = -1) -> Array:
    """Circular ``out[i] = x[(i - shift) mod T]`` along ``axis``."""
    t = x.shape[axis]
    shift %= t
    if shift == 0:
        return x
    return pltpu.roll(x, shift, axis % x.ndim)


def shift_right(x: Array, d: int, fill, axis: int = -1) -> Array:
    """``x[i] <- x[i-d]`` along ``axis`` (static d), front-filled."""
    return jnp.where(lane_iota(x, axis) >= d, lane_roll(x, d, axis), fill)


def shift_left(x: Array, d: int, fill, axis: int = -1) -> Array:
    """``x[i] <- x[i+d]`` along ``axis`` (static d), back-filled."""
    t = x.shape[axis]
    return jnp.where(lane_iota(x, axis) < t - d, lane_roll(x, t - d, axis),
                     fill)


def lane_at(x: Array, i: int) -> Array:
    """Lane ``i`` of every row as a ``[rows, 1]`` column (masked sum)."""
    return jnp.sum(jnp.where(lane_iota(x) == i, x, jnp.zeros((), x.dtype)),
                   axis=-1, keepdims=True)


def prefix_sum(x: Array) -> Array:
    """Inclusive prefix sum along the last axis: log2(T) roll-adds."""
    t = x.shape[-1]
    d = 1
    while d < t:
        x = x + shift_right(x, d, 0)
        d *= 2
    return x


def _partner(x: Array, j: int, lower: Array, axis: int) -> Array:
    """``x[i ^ j]``: the lane ``j`` above for lanes with bit ``j`` clear
    (``lower``), the lane ``j`` below otherwise."""
    t = x.shape[axis]
    return jnp.where(lower, lane_roll(x, t - j, axis), lane_roll(x, j, axis))


def _lex_less(a: tuple[Array, ...], b: tuple[Array, ...]) -> Array:
    less = a[0] < b[0]
    eq = a[0] == b[0]
    for x, y in zip(a[1:], b[1:]):
        less = less | (eq & (x < y))
        eq = eq & (x == y)
    return less


def _compare_exchange(operands: tuple[Array, ...], num_keys: int, j: int,
                      up: Array | None, axis: int) -> tuple[Array, ...]:
    """One network sweep: lane ``i`` and its partner ``i ^ j`` are ordered
    ascending where ``up`` (None: everywhere), descending elsewhere.  Ties
    never swap."""
    lower = (lane_iota(operands[0], axis) & j) == 0
    par = tuple(_partner(x, j, lower, axis) for x in operands)
    lo = tuple(jnp.where(lower, s, p)
               for s, p in zip(operands[:num_keys], par[:num_keys]))
    hi = tuple(jnp.where(lower, p, s)
               for s, p in zip(operands[:num_keys], par[:num_keys]))
    swap = _lex_less(hi, lo)
    if up is not None:
        swap = (up & swap) | (~up & _lex_less(lo, hi))
    return tuple(jnp.where(swap, p, s) for s, p in zip(operands, par))


def bitonic_sort_tile(operands: tuple[Array, ...], num_keys: int,
                      axis: int = -1) -> tuple[Array, ...]:
    """Bitonic sort along ``axis``, lexicographic by the leading
    ``num_keys`` operands (the rest ride along as payload)."""
    t = operands[0].shape[axis]
    assert t & (t - 1) == 0, f"tile length must be a power of two, got {t}"
    lane = lane_iota(operands[0], axis)
    k = 2
    while k <= t:
        up = None if k == t else (lane & k) == 0
        j = k // 2
        while j >= 1:
            operands = _compare_exchange(operands, num_keys, j, up, axis)
            j //= 2
        k *= 2
    return operands


def _reverse_odd_runs(x: Array, run: int) -> Array:
    """Reverse every odd ``run``-long run: lane ``p`` of such a run takes
    lane ``p ^ (run - 1)``, one partner selection per bit of ``run - 1``."""
    lane = lane_iota(x)
    odd = (lane & run) != 0
    b = 1
    while b < run:
        x = jnp.where(odd, _partner(x, b, (lane & b) == 0, -1), x)
        b *= 2
    return x


def bitonic_merge_tile(operands: tuple[Array, ...], num_keys: int,
                       run: int) -> tuple[Array, ...]:
    """Multiway merge of T/run presorted ascending runs along the last axis.

    The pane path's in-VMEM window assembly: log2(T/run) rounds of
    (reverse odd runs, clean doubled blocks) — total depth
    ~ log(T/run)*log(T) compare-exchange sweeps instead of the full
    log^2(T) re-sort of :func:`bitonic_sort_tile`.
    """
    t = operands[0].shape[-1]
    assert t & (t - 1) == 0 and run >= 1 and run & (run - 1) == 0 \
        and t % run == 0, f"need power-of-two tile/run, got T={t} run={run}"
    length = run
    while length < t:
        operands = tuple(_reverse_odd_runs(x, length) for x in operands)
        length *= 2
        j = length // 2
        while j >= 1:
            operands = _compare_exchange(operands, num_keys, j, None, -1)
            j //= 2
    return operands


def tile_segmented_scan(flags: Array, state: Any, combiner: Combiner, *,
                        reverse: bool = False) -> Any:
    """Inclusive segmented scan across the last axis of every state leaf.

    Hillis–Steele: log2(T) rounds of (shift, combine, select) — the software
    unrolling of the PRRA's prefix-scan entity network (entities ``n``).
    ``flags`` marks segment starts and needs ``flags[..., 0]`` set (a
    well-formed labelling starts a segment at lane 0), which keeps the
    shifted-in fill values dead.  ``reverse=True`` scans right to left:
    ``flags`` then marks segment *ends* and needs the last lane set.
    """
    t = flags.shape[-1]
    assert t & (t - 1) == 0, f"tile length must be a power of two, got {t}"
    shift = shift_left if reverse else shift_right
    f = flags.astype(jnp.int32)
    s = state
    d = 1
    while d < t:
        prev_s = jax.tree.map(lambda x: shift(x, d, 0), s)
        merged = combiner.op(s, prev_s) if reverse else combiner.op(prev_s, s)
        s = jax.tree.map(lambda m, x: jnp.where(f != 0, x, m), merged, s)
        f = f | shift(f, d, 1)  # out-of-range counts as boundary
        d *= 2
    return s


def butterfly_compact(valid: Array, arrays: tuple[Array, ...],
                      fills: tuple[Any, ...]) -> tuple[tuple[Array, ...], Array]:
    """Dense left-compaction of ``valid`` lanes — the reverse butterfly.

    Each valid element's destination is its rank (exclusive prefix-sum of
    ``valid``); the required displacement ``d = i - rank(i)`` is monotone
    non-decreasing, so routing one displacement bit per round (LSB first,
    static shifts of 1, 2, 4, ...) is collision-free — the textbook property
    the PRRA's reverse butterfly exploits, with wires replaced by vector
    shifts.

    Returns (compacted arrays with invalid tail filled, ``[rows, 1]`` count
    of valid lanes).
    """
    t = valid.shape[-1]
    assert t & (t - 1) == 0
    v = valid.astype(jnp.int32)
    inclusive = prefix_sum(v)
    disp = jnp.where(valid, lane_iota(v) - (inclusive - v), 0)
    count = lane_at(inclusive, t - 1)

    arrs = arrays
    b = 1
    while b < t:
        in_arrs = tuple(shift_left(a, b, fl) for a, fl in zip(arrs, fills))
        in_disp = shift_left(disp, b, 0)
        arrive = (shift_left(v, b, 0) != 0) & ((in_disp & b) != 0)
        stay = (v != 0) & ((disp & b) == 0)
        arrs = tuple(jnp.where(arrive, ia, a) for ia, a in zip(in_arrs, arrs))
        disp = jnp.where(arrive, in_disp - b, disp)
        v = (arrive | stay).astype(jnp.int32)
        b *= 2
    arrs = tuple(jnp.where(v != 0, a, jnp.full_like(a, fl))
                 for a, fl in zip(arrs, fills))
    return arrs, count
