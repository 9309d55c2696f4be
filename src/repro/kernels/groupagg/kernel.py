"""Pallas TPU kernel: the FUSED group-by-aggregate engine (paper Fig. 2).

All five steps of the paper's engine execute in a single VMEM pass per tile —
this is the fusion the paper sells (one scan network doing aggregation *and*
compaction, ``2P + PRRA`` instead of ``3P + 2 PRRA``):

  (b) mark last-of-group        shifted compares (the entities ``t``)
  (c) rolling segmented scan    Hillis–Steele in VMEM (entities ``n``)
  (d) finalize + rolling carry  VMEM scratch across the sequential grid
                                (entities ``n'`` — count wider than one tile)
  (e) round-robin compaction    reverse butterfly = log2(T) shift+select
                                rounds (collision-free monotone routing)

Tile-boundary protocol (the paper's step (a), one-batch lookahead buffer):
the trailing run of tile ``i`` is never emitted by tile ``i``; it is either
extended or emitted by tile ``i+1``.  The wrapper appends one tile of
``PAD_GROUP`` sentinels so the final real group always closes.

Outputs are *per-tile compacted*: ``groups/values[tile, T]`` with a
``count[tile]`` — the engine's per-batch valid ports.  The cheap final stitch
(offset by prefix-sums of counts) happens outside the kernel, on the already
T-times-smaller compacted stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.combiners import Combiner
from repro.core.engine import PAD_GROUP
from repro.kernels import common


def _kernel(g_ref, k_ref, og_ref, ov_ref, pg_ref, pv_ref, *pstate_refs,
            combiner: Combiner):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        pg_ref[...] = jnp.full((1, 1), PAD_GROUP, jnp.int32)
        pv_ref[...] = jnp.zeros((1, 1), jnp.int32)
        for r in pstate_refs:
            r[...] = jnp.zeros((1, 1), r.dtype)

    g = g_ref[...]
    k = k_ref[...]
    t = g.shape[-1]

    # ---- (b) entities t: run boundaries from shifted compares ----
    sentinel = jnp.iinfo(jnp.int32).min  # no valid group id (contract: > INT32_MIN)
    starts = g != common.shift_right(g, 1, sentinel)   # lane 0 forced start
    ends = g != common.shift_left(g, 1, sentinel)
    lane = common.lane_iota(g)
    ends = ends & (lane != t - 1)                      # trailing run is withheld

    # ---- (c) entities n: in-tile rolling segmented prefix scan ----
    state = combiner.lift(k)
    treedef = jax.tree.structure(state)
    scanned = common.tile_segmented_scan(starts, state, combiner)

    # merge the carried (pending) run if it continues into this tile
    pending_g = pg_ref[...]
    pending_valid = pv_ref[...] != 0
    pending_state = jax.tree.unflatten(treedef, [r[...] for r in pstate_refs])
    first_run = common.prefix_sum(starts.astype(jnp.int32)) == 1
    g0 = common.lane_at(g, 0)
    merge_mask = first_run & pending_valid & (pending_g == g0)
    merged_all = combiner.op(pending_state, scanned)
    merged = jax.tree.map(
        lambda m, s: jnp.where(merge_mask, m, s), merged_all, scanned)

    # ---- (d) entities n': finalize at run ends ----
    values = combiner.finalize(merged)
    emit = ends & (g != PAD_GROUP)

    # ---- (e) reverse butterfly: dense round-robin compaction ----
    (cg, cv), _ = common.butterfly_compact(
        emit, (g, values), (PAD_GROUP, jnp.zeros((), values.dtype)))

    # emit the pending run if this tile does not continue it
    emit_pending = pending_valid & (pending_g != g0) & (pending_g != PAD_GROUP)
    pend_val = combiner.finalize(pending_state)
    lane0 = lane == 0
    cg_shift = jnp.where(lane0, pending_g, common.shift_right(cg, 1, PAD_GROUP))
    cv_shift = jnp.where(lane0, pend_val, common.shift_right(cv, 1, 0))
    og_ref[0] = jnp.where(emit_pending, cg_shift, cg)
    ov_ref[0] = jnp.where(emit_pending, cv_shift, cv)

    # ---- new pending = this tile's trailing run ----
    last_g = common.lane_at(g, t - 1)
    pg_ref[...] = last_g
    pv_ref[...] = (last_g != PAD_GROUP).astype(jnp.int32)
    for r, leaf in zip(pstate_refs, jax.tree.leaves(merged)):
        r[...] = common.lane_at(leaf, t - 1)


def groupagg_pallas(groups, keys, combiner: Combiner, *, tile: int,
                    out_dtype, interpret: bool):
    """groups/keys: [1, N] with N % tile == 0, PAD_GROUP-closed.  Returns
    per-tile compacted ``(og, ov [tiles, tile], oc [tiles])``; ``og`` is
    PAD_GROUP past each tile's emitted groups, which is how ``oc``
    counts them."""
    n = groups.shape[-1]
    num_tiles = n // tile
    probe = combiner.lift(jnp.zeros((1,), keys.dtype))
    leaf_dtypes = [l.dtype for l in jax.tree.leaves(probe)]

    kern = functools.partial(_kernel, combiner=combiner)
    block = pl.BlockSpec((1, tile), lambda i: (0, i))
    out_block = pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0))
    og, ov = pl.pallas_call(
        kern,
        grid=(num_tiles,),
        in_specs=[block, block],
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles, 1, tile), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles, 1, tile), out_dtype),
        ],
        scratch_shapes=(
            [pltpu.VMEM((1, 1), jnp.int32), pltpu.VMEM((1, 1), jnp.int32)]
            + [pltpu.VMEM((1, 1), d) for d in leaf_dtypes]),
        interpret=interpret,
    )(groups, keys)
    og, ov = og[:, 0, :], ov[:, 0, :]
    return og, ov, jnp.sum((og != PAD_GROUP).astype(jnp.int32), axis=-1)
