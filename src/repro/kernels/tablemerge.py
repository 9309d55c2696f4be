"""Pallas TPU kernels of the pane-partial table's merge: the short-distance
parts of its bitonic merge and of its butterfly compaction, in VMEM.

Both work on a table's columns in the ``[rows, lanes]`` view of
:mod:`repro.core.panetable`'s merge, one tile of ``tile`` rows per grid
step, so that every step of distance under a tile (``tile * lanes`` lanes)
costs one pass over HBM instead of one each.  A tile that no step would
change (already in order; no lane to move) is copied as it is, which
after a push is most of a table:

* :func:`merge_tiles` — the last sweeps of an ascending bitonic merge, those
  whose partner lies in the same tile (row rotations, then lane rotations);
* :func:`compact_tiles` — the low rounds of a butterfly compaction, those
  that move a lane by less than a tile.  A lane ends them at most a tile
  to the left of where it began, so each step reads its tile and the next
  one and writes its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def tile_rows(rows: int, ncols: int) -> int:
    """Rows of a tile for ``ncols`` columns: about 2,048 rows of int32 over
    all columns (1 MiB) a step, kept in VMEM with its temporaries."""
    return min(rows, 1 << ((2048 // ncols).bit_length() - 1))


def _copy(ins, outs, rows: int):
    for r, o in zip(ins, outs):
        o[...] = r[:rows]


def _merge_kernel(*refs, ncols: int):
    ins, outs = refs[:ncols], refs[ncols:]
    g, p = ins[0][...], ins[1][...]
    s, w = g.shape
    # a tile already in order stays so through every sweep left (ties
    # never swap): most of a table's tiles, which no row of the push
    # reached, are copied
    ng, np_ = _to_start(g, 1), _to_start(p, 1)
    last = (common.lane_iota(g, 0) == s - 1) \
        & (common.lane_iota(g, 1) == w - 1)
    ordered = last | (g < ng) | ((g == ng) & (p <= np_))
    done = jnp.min(ordered.astype(jnp.int32)) == 1

    @pl.when(done)
    def _as_is():
        _copy(ins, outs, s)

    @pl.when(jnp.logical_not(done))
    def _sweeps():
        cols = tuple(r[...] for r in ins)
        j = s * w // 2
        while j >= 1:
            axis, dist = (-1, j) if j < w else (0, j // w)
            cols = common._compare_exchange(cols, 2, dist, None, axis)
            j //= 2
        for r, x in zip(outs, cols):
            r[...] = x


def merge_tiles(cols, tile: int, *, interpret: bool):
    """The sweeps of distance under ``tile`` rows of an ascending bitonic
    merge of ``cols`` (a pytree of ``[rows, lanes]`` leaves, keyed by its
    first two), each tile in VMEM."""
    leaves, tree = jax.tree.flatten(cols)
    rows, lanes = leaves[0].shape
    block = pl.BlockSpec((tile, lanes), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_merge_kernel, ncols=len(leaves)),
        grid=(rows // tile,),
        in_specs=[block] * len(leaves),
        out_specs=[block] * len(leaves),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves],
        interpret=interpret,
    )(*leaves)
    return jax.tree.unflatten(tree, out)


def _to_start(x, d: int):
    """Lane ``i`` of ``x``'s row-major order takes lane ``i + d`` (``d``
    static), circular over ``x``."""
    rows, w = x.shape
    q, s = divmod(d, w)
    x = common.lane_roll(x, rows - q, 0)
    if s == 0:
        return x
    x = common.lane_roll(x, w - s, 1)
    return jnp.where(common.lane_iota(x, 1) < w - s, x,
                     common.lane_roll(x, rows - 1, 0))


def _compact_kernel(*refs, ncols: int):
    ins, outs = refs[:2 * ncols + 2], refs[2 * ncols + 2:]
    last = pl.program_id(0) == pl.num_programs(0) - 1
    s, w = outs[0].shape
    # this tile and the next (none after the last): every lane that ends
    # in this tile began in one of them
    disp = jnp.concatenate([ins[0][...], jnp.where(last, -1, ins[1][...])])
    # no lane to move by less than a tile (as before the table's first
    # dead row): the tile stays as it is
    still = (disp < 0) | ((disp & (s * w - 1)) == 0)
    done = jnp.min(still.astype(jnp.int32)) == 1

    @pl.when(done)
    def _as_is():
        _copy(ins[::2], outs, s)

    @pl.when(jnp.logical_not(done))
    def _rounds():
        d = disp
        cols = [jnp.concatenate([ins[k][...], ins[k + 1][...]])
                for k in range(2, 2 * ncols + 2, 2)]
        b = 1
        while b < s * w:
            came = _to_start(d, b)
            arrive = (came > 0) & ((came & b) != 0)
            stay = (d >= 0) & ((d & b) == 0)
            cols = [jnp.where(arrive, _to_start(x, b), x) for x in cols]
            d = jnp.where(arrive, came, jnp.where(stay, d, -1))
            b *= 2
        for r, x in zip(outs, [d, *cols]):
            r[...] = x[:s]


def compact_tiles(disp, cols, tile: int, *, interpret: bool):
    """The rounds of a butterfly compaction that move a lane by less than
    ``tile`` rows.  ``disp`` (``[rows, lanes]`` int32) is each lane's
    displacement to the left, -1 on the lanes that hold no row; ``cols``
    (a pytree of leaves shaped like it) move with it.  Returns ``(disp,
    cols)`` after those rounds, the displacement left on each row."""
    leaves, tree = jax.tree.flatten(cols)
    rows, lanes = disp.shape
    n = rows // tile
    here = pl.BlockSpec((tile, lanes), lambda i: (i, 0))
    after = pl.BlockSpec((tile, lanes), lambda i: (jnp.minimum(i + 1, n - 1),
                                                   0))
    args = [a for x in (disp, *leaves) for a in (x, x)]
    out = pl.pallas_call(
        functools.partial(_compact_kernel, ncols=len(leaves)),
        grid=(n,),
        in_specs=[here, after] * (len(leaves) + 1),
        out_specs=[here] * (len(leaves) + 1),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (disp, *leaves)],
        interpret=interpret,
    )(*args)
    return out[0], jax.tree.unflatten(tree, out[1:])
