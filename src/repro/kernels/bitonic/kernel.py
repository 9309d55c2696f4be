"""Pallas TPU kernel: in-VMEM bitonic sorting network (FLiMS adaptation).

The paper feeds its engine from an FPGA merge sorter.  On TPU the analogue
for window/tile-scale sorts (the paper's SWAG windows are <= 4K tuples, which
fit VMEM) is a bitonic network executed entirely on-chip:

  * the ``p ^ j`` partner is one of two lane rolls, picked by bit ``j`` of
    the lane index — every compare-exchange is two rolls and a select,
    **no gathers**;
  * log2(T)*(log2(T)+1)/2 sweeps, each O(T) vector work, fixed at trace time
    (the FPGA's fixed wiring becomes a fixed unrolled schedule);
  * multi-operand: sorts (group, key) lexicographically and drags any number
    of payload columns along (struct-of-arrays).

Each grid row sorts an independent tile (batched sorting, e.g. SWAG windows).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels import common


def _kernel(*refs, n_ops: int, num_keys: int):
    in_refs = refs[:n_ops]
    out_refs = refs[n_ops:]
    operands = tuple(r[...] for r in in_refs)
    out = common.bitonic_sort_tile(operands, num_keys=num_keys)
    for r, o in zip(out_refs, out):
        r[...] = o


def bitonic_pallas(operands: tuple, num_keys: int, *, interpret: bool) -> tuple:
    """Sort each row of [R, T] operands along the last axis; T power of two."""
    r, t = operands[0].shape
    kern = functools.partial(_kernel, n_ops=len(operands), num_keys=num_keys)
    block = pl.BlockSpec((1, t), lambda i: (i, 0))
    out = pl.pallas_call(
        kern,
        grid=(r,),
        in_specs=[block] * len(operands),
        out_specs=[block] * len(operands),
        out_shape=[jax.ShapeDtypeStruct((r, t), o.dtype) for o in operands],
        interpret=interpret,
    )(*operands)
    return tuple(out)
