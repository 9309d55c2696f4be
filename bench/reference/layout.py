"""The result layout every reference fills: the engine's documented
``AggResult`` (``[rows, lanes]`` groups, values and validity, groups
compacted ascending to the front of each row, the pad id beyond, and a
per-row group count); and the bytes such a result takes."""
from __future__ import annotations

import numpy as np

#: the pad group id of the engine's result layout (int32 max)
PAD_GROUP = np.iinfo(np.int32).max
#: bytes of one result element: group ids, counts and distinct counts are
#: int32, validity one byte, other ops the key's width
_GROUP_BYTES, _VALID_BYTES, _COUNT_BYTES = 4, 1, 4
_COUNTING_OPS = ("count", "distinct_count")


def each_batch(answer, pool, pushes, query) -> dict:
    """``{push: answer(batch, query)}`` for a stateless query, where push
    ``i`` carried ``pool[i % len(pool)]``; each batch is answered once."""
    by_batch = {}
    out = {}
    for i in pushes:
        b = i % len(pool)
        if b not in by_batch:
            by_batch[b] = answer(pool[b], query)
        out[i] = by_batch[b]
    return out


def scatter_rows(rows: int, lanes: int, row, lane, group, values: dict):
    """Lay per-(row, group) answers out as the engine's result arrays.

    ``row``/``lane``/``group`` and each ``values`` column are flat arrays of
    one entry per answer; ``lane`` is the group's rank within its row."""
    if lane.size and lane.max() >= lanes:
        raise ValueError(f"a row holds more than {lanes} groups")
    out_groups = np.full((rows, lanes), PAD_GROUP, np.int64)
    out_groups[row, lane] = group
    valid = np.zeros((rows, lanes), bool)
    valid[row, lane] = True
    out = {}
    for name, col in values.items():
        a = np.zeros((rows, lanes), np.int64)
        a[row, lane] = col
        out[name] = a
    num = np.bincount(row, minlength=rows).astype(np.int64)
    return {"groups": out_groups, "values": out, "valid": valid,
            "num_groups": num}


def reduce_ranges(keys, a, b, ops):
    """Each op of ``keys[a[i]:b[i]]`` for every ``i``, exact in int64:
    min, max, sum, count, median (the lower one) and distinct_count.
    Every range must be non-empty."""
    if np.any(b <= a):
        raise ValueError("empty range")
    k = np.append(keys.astype(np.int64), 0)      # room for b == len(keys)
    bounds = np.stack([a, b], axis=1).ravel()    # reduceat over [a_i, b_i)
    out = {}
    for op in ops:
        if op == "min":
            out[op] = np.minimum.reduceat(k, bounds)[::2]
        elif op == "max":
            out[op] = np.maximum.reduceat(k, bounds)[::2]
        elif op == "sum":
            prefix = np.concatenate([[0], np.cumsum(k[:-1])])
            out[op] = prefix[b] - prefix[a]
        elif op == "count":
            out[op] = (b - a).astype(np.int64)
        elif op in ("median", "distinct_count"):
            col = np.empty(a.size, np.int64)
            for i, (lo, hi) in enumerate(zip(a, b)):
                s = np.sort(k[lo:hi])
                col[i] = (s[(hi - lo - 1) // 2] if op == "median"
                          else np.count_nonzero(np.diff(s)) + 1)
            out[op] = col
        else:
            raise ValueError(f"the reference has no op {op!r}")
    return out


def column_width(config: dict, name: str) -> int:
    col = next(c for c in config["data"]["columns"] if c["name"] == name)
    return np.dtype(col.get("dtype", "int32")).itemsize


def push_bytes(config: dict, tuples: int, rows: int, lanes: int) -> int:
    """Bytes a push must move through HBM: its ``tuples`` read once (every
    column) and a ``[rows, lanes]`` result written once."""
    key_bytes = column_width(config, "keys")
    per_lane = _GROUP_BYTES + _VALID_BYTES + sum(
        _COUNT_BYTES if op in _COUNTING_OPS else key_bytes
        for op in config["query"]["ops"])
    inputs = tuples * sum(np.dtype(c.get("dtype", "int32")).itemsize
                          for c in config["data"]["columns"])
    return inputs + rows * lanes * per_lane + rows * _COUNT_BYTES
