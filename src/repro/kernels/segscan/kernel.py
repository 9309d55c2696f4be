"""Pallas TPU kernel: tiled rolling segmented scan (the PRRA scan network).

Grid = sequential tiles of ``T`` lanes (TPU grids execute in order, which is
what makes the *rolling* carry sound — the same property the paper gets from
its pipeline registers).  Per tile:

  1. load flags + state leaves into VMEM ((1, T) blocks, T a multiple of 128);
  2. in-tile Hillis–Steele segmented scan (log2 T rounds of shift+combine —
     the butterfly dataflow);
  3. merge the carry (previous tile's trailing run) into the leading open run;
  4. persist the new carry (last lane's merged state) in VMEM scratch.

The combiner is closed over at trace time (the ``function_select`` of the
hardware becomes a specialization axis), so one kernel source serves every
operator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.combiners import Combiner
from repro.kernels import common


def _kernel(flags_ref, *refs, combiner: Combiner, n_leaves: int):
    in_refs = refs[:n_leaves]
    out_refs = refs[n_leaves:2 * n_leaves]
    cflag_ref = refs[2 * n_leaves]
    carry_refs = refs[2 * n_leaves + 1:]

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cflag_ref[...] = jnp.zeros((1, 1), jnp.int32)
        for r in carry_refs:
            r[...] = jnp.zeros((1, 1), r.dtype)

    flags = flags_ref[...]
    leaves = tuple(r[...] for r in in_refs)
    treedef = combiner_treedef(combiner, leaves)
    state = jax.tree.unflatten(treedef, list(leaves))
    t = flags.shape[-1]

    # force a tile-local segment start at lane 0; the true continuation is
    # re-attached through the carry below
    lane0 = common.lane_iota(flags) == 0
    scanned = common.tile_segmented_scan((flags != 0) | lane0, state,
                                         combiner)

    # lanes still inside the run that crosses the tile boundary
    open_mask = ((common.prefix_sum((flags != 0).astype(jnp.int32)) == 0)
                 & (cflag_ref[...] != 0))
    carry_state = jax.tree.unflatten(treedef, [r[...] for r in carry_refs])
    merged_all = combiner.op(carry_state, scanned)
    merged = jax.tree.map(
        lambda m, s: jnp.where(open_mask, m, s), merged_all, scanned)

    for r, leaf in zip(out_refs, jax.tree.leaves(merged)):
        r[...] = leaf
    for r, leaf in zip(carry_refs, jax.tree.leaves(merged)):
        r[...] = common.lane_at(leaf, t - 1)
    cflag_ref[...] = jnp.ones((1, 1), jnp.int32)


def combiner_treedef(combiner: Combiner, leaves):
    """Treedef of the combiner state, recovered from a probe lift."""
    probe = combiner.lift(jnp.zeros((1,), jnp.int32))
    return jax.tree.structure(probe)


def segscan_pallas(flags, leaves: tuple, combiner: Combiner, *, tile: int,
                   interpret: bool) -> tuple:
    """Raw pallas_call wrapper.  flags/leaves are [1, N] with N % tile == 0."""
    n = flags.shape[-1]
    num_tiles = n // tile
    n_leaves = len(leaves)
    kern = functools.partial(_kernel, combiner=combiner, n_leaves=n_leaves)

    block = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        kern,
        grid=(num_tiles,),
        in_specs=[block] * (1 + n_leaves),
        out_specs=[block] * n_leaves,
        out_shape=[jax.ShapeDtypeStruct((1, n), l.dtype) for l in leaves],
        scratch_shapes=(
            [pltpu.VMEM((1, 1), jnp.int32)]
            + [pltpu.VMEM((1, 1), l.dtype) for l in leaves]),
        interpret=interpret,
    )(flags, *leaves)
    return tuple(out)
