"""Two-stack SWAG, flip-batched: replay-free time windows for
invertible-free ops.

Pane replay re-aggregates every tuple of every window — O(NW * wcap) work —
which is the only correct option for ops without an inverse (min/max: you
cannot "subtract" an evicted tuple).  Tangwongsan et al.'s two-stack
algorithm fixes this for in-order sliding windows: a *front* stack holds
suffix aggregates of the older tuples, a *back* stack holds a running
prefix of the newer ones, and every window answer is one combine
``op(front_top, back_agg)``; when the front stack empties, the back stack
is **flipped** into suffix form.  Amortised O(1) per tuple.

The stack operations are sequential, but over a *batch* the flip points
depend only on the window boundary indices — never on tuple values — so
the whole schedule is computed host-side and the per-tuple work becomes
data-parallel:

  * :func:`epoch_layout` walks the ``NW`` window ranges once (host side):
    a new **epoch** begins at every flip (the first window whose start
    passes the previous flip point ``hi``); epoch ``e`` fixes
    ``hi_e = ends[first window]``.
  * per epoch, one **suffix scan** over the front region
    ``[f_lo_e, hi_e)`` and one **prefix scan** over the back region
    ``[hi_e, max ends in epoch)`` — the flip, batched.  Both regions fit
    in ``wcap`` lanes (each is bounded by one window's tuple count), so
    the scans are two ``[NE, wcap]`` Hillis–Steele sweeps
    (:func:`flip_scans`) — the Pallas stack-flip kernel runs the same
    sweeps per grid row in VMEM (``repro.kernels.swag.kernel.
    twostack_flip_pallas``).
  * every window then reads **two lanes**: its front suffix at
    ``start - f_lo`` and its back prefix at ``end - hi``, combined with
    the op's monoid — O(N + NW) total instead of O(NW * wcap).

Applies to ungrouped queries over :data:`repro.core.swag.PARTIAL_OPS`
(single-array monoid states); everything else takes the replay strategy.
Element-exact vs. replay: both evaluate the same monoid over the same
window multiset, associativity is the only freedom.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.combiners import get_combiner

Array = jax.Array


class EpochLayout(NamedTuple):
    """Host-side flip schedule: window ``j`` belongs to epoch
    ``epoch_id[j]``; epoch ``e``'s front region is ``[f_lo[e], hi[e])``
    and its back region starts at ``hi[e]``."""
    epoch_id: np.ndarray  # [NW]
    f_lo: np.ndarray      # [NE]
    hi: np.ndarray        # [NE] flip points
    b_hi: np.ndarray      # [NE] back region end (max window end in epoch)


def epoch_layout(starts: np.ndarray, ends: np.ndarray) -> EpochLayout:
    """Walk the window ranges once, flipping whenever the front region
    would be empty (``start >= hi``) — the two-stack flip rule with the
    value-independent schedule made explicit."""
    nw = starts.shape[0]
    epoch_id = np.zeros(nw, np.int64)
    f_lo, hi, b_hi = [], [], []
    cur = 0
    for j in range(nw):
        if not f_lo or starts[j] >= cur:
            f_lo.append(int(starts[j]))
            cur = int(ends[j])
            hi.append(cur)
            b_hi.append(cur)
        epoch_id[j] = len(f_lo) - 1
        b_hi[-1] = max(b_hi[-1], int(ends[j]))
    return EpochLayout(epoch_id, np.asarray(f_lo, np.int64),
                       np.asarray(hi, np.int64), np.asarray(b_hi, np.int64))


def _region(keys: Array, lo: Array, length: Array, wcap: int):
    """Gather ``[NE, wcap]`` slices ``keys[lo : lo + length]`` with a
    liveness mask (static width, clipped gather)."""
    n = keys.shape[-1]
    idx = lo[:, None] + jnp.arange(wcap, dtype=jnp.int32)[None, :]
    live = jnp.arange(wcap)[None, :] < length[:, None]
    idx = jnp.clip(idx, 0, max(n - 1, 0))
    return keys[idx], live


def _shift_right(x: Array, d: int, fill) -> Array:
    """x[i] <- x[i-d] along the last axis (static d), front-filled."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _shift_left(x: Array, d: int, fill) -> Array:
    """x[i] <- x[i+d] along the last axis (static d), back-filled."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([x[..., d:], pad], axis=-1)


def flip_scans(kf: Array, vf: Array, kb: Array, vb: Array, names,
               key_dtype, *, shift_left=_shift_left,
               shift_right=_shift_right) -> dict:
    """The batched flip: per op, an inclusive *suffix* scan over the front
    slices and an inclusive *prefix* scan over the back slices (masked
    lanes pinned to the op's identity).  Pure ``jnp`` over the last axis —
    the same code runs batched ``[NE, wcap]`` on the reference backend and
    per-row inside the Pallas kernel, which passes its own lane shifts
    (``shift_left``/``shift_right``, ``(x, d, fill)``).  Returns
    ``{name: (front_suffix, back_prefix)}``."""
    wcap = kf.shape[-1]
    out = {}
    for name in names:
        comb = get_combiner(name)
        ident = comb.identity((), key_dtype)
        f = jax.tree.map(lambda s, i: jnp.where(vf, s, i),
                         comb.lift(kf), ident)
        b = jax.tree.map(lambda s, i: jnp.where(vb, s, i),
                         comb.lift(kb), ident)
        d = 1
        while d < wcap:
            f = comb.op(f, jax.tree.map(
                lambda s, i: shift_left(s, d, i), f, ident))
            b = comb.op(jax.tree.map(
                lambda s, i: shift_right(s, d, i), b, ident), b)
            d *= 2
        out[name] = (f, b)
    return out


def twostack_time_windows(keys_sorted: Array, layout, epochs: EpochLayout,
                          names, *, use_kernel: bool = False,
                          interpret: bool = False):
    """Evaluate every time window of one batch via the flip-batched
    two-stack.  ``keys_sorted`` is the ts-sorted value column; ``layout``
    a :class:`repro.core.eventtime.TimeLayout`; ``names`` a tuple of
    :data:`repro.core.swag.PARTIAL_OPS` op names.

    Returns ``(values {name: [NW]}, counts [NW])`` — the ungrouped
    per-window answers (zero where the window is empty) and tuple counts.
    """
    key_dtype = keys_sorted.dtype
    wcap = layout.wcap
    nw = layout.starts.shape[0]
    if nw == 0:
        return ({name: jnp.zeros((0,), _out_dtype(name, key_dtype))
                 for name in names}, jnp.zeros((0,), jnp.int32))

    f_lo = jnp.asarray(epochs.f_lo, jnp.int32)
    hi = jnp.asarray(epochs.hi, jnp.int32)
    kf, vf = _region(keys_sorted, f_lo,
                     jnp.asarray(epochs.hi - epochs.f_lo, jnp.int32), wcap)
    kb, vb = _region(keys_sorted, hi,
                     jnp.asarray(epochs.b_hi - epochs.hi, jnp.int32), wcap)

    if use_kernel:
        from repro.kernels.swag.kernel import twostack_flip_pallas
        scans = twostack_flip_pallas(kf, vf, kb, vb, names,
                                     interpret=interpret)
    else:
        scans = flip_scans(kf, vf, kb, vb, names, key_dtype)

    eid = jnp.asarray(epochs.epoch_id, jnp.int32)
    starts = jnp.asarray(layout.starts, jnp.int32)
    ends = jnp.asarray(layout.ends, jnp.int32)
    cnt = ends - starts
    df = starts - f_lo[eid]          # front suffix lane, in [0, wcap]
    db = ends - hi[eid]              # back prefix length, in [0, wcap]

    values = {}
    for name in names:
        comb = get_combiner(name)
        ident = comb.identity((), key_dtype)
        fsuf, bpre = scans[name]
        front = jax.tree.map(
            lambda s, i: jnp.where(df < wcap,
                                   s[eid, jnp.minimum(df, wcap - 1)], i),
            fsuf, ident)
        back = jax.tree.map(
            lambda s, i: jnp.where(db > 0,
                                   s[eid, jnp.maximum(db - 1, 0)], i),
            bpre, ident)
        v = comb.finalize(comb.op(front, back))
        values[name] = jnp.where(cnt > 0, v, jnp.zeros((), v.dtype))
    return values, cnt


def _out_dtype(name: str, key_dtype):
    comb = get_combiner(name)
    return jax.eval_shape(
        lambda x: comb.finalize(comb.lift(x)),
        jax.ShapeDtypeStruct((1,), key_dtype)).dtype
