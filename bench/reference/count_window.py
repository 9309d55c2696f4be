"""Grouped aggregates over a global count window (sliding-window
aggregation with groups, the paper's SWAG).

Window ``e`` covers stream tuples ``[e*wa, e*wa + ws)``.  Its answer is
every group present in it, ascending, with each op of that group's keys
inside the window.  Rows are windows; each row has ``ws`` lanes.  Each
push is answered on its own (the query holds no state between pushes).
Without a ``groups`` column (``group_by: false``) the stream is one group,
group 0.
"""
from __future__ import annotations

import functools

import numpy as np

from reference.layout import each_batch, push_bytes, reduce_ranges, \
    scatter_rows


def _windows(groups, keys, *, first: int, length: int, ws: int, wa: int,
             ops):
    """Answer every window from tuples ``[e*wa + first, e*wa + first +
    length)``; the exact window is ``first=0, length=ws``."""
    n = groups.size
    nw = (n - ws) // wa + 1 if n >= ws else 0
    idx = (np.arange(nw)[:, None] * wa + first
           + np.arange(length)[None, :])
    g = groups[idx].astype(np.int64).ravel()
    k = keys[idx].astype(np.int64).ravel()
    win = np.repeat(np.arange(nw), length)
    order = np.lexsort((g, win))              # by window, then group
    g, k, win = g[order], k[order], win[order]
    new = np.ones(g.size, bool)
    new[1:] = (g[1:] != g[:-1]) | (win[1:] != win[:-1])
    starts = np.flatnonzero(new)
    ends = np.append(starts[1:], g.size)
    vals = reduce_ranges(k, starts, ends, ops)
    row = win[starts]
    lane = np.arange(starts.size) - np.searchsorted(row, row)
    return scatter_rows(nw, ws, row, lane, g[starts], vals)


def _answer(batch, query, *, control: bool):
    w = query["window"]
    first = 1 if control else 0
    keys = batch["keys"]
    groups = batch.get("groups")
    if groups is None:
        groups = np.zeros(keys.size, np.int64)
    return _windows(groups, keys, first=first,
                    length=w["ws"] - first, ws=w["ws"], wa=w["wa"],
                    ops=query["ops"])


def evaluate(pool, pushes, query) -> dict:
    return each_batch(functools.partial(_answer, control=False), pool,
                      pushes, query)


def control(pool, pushes, query) -> dict:
    """Each window one tuple short: its oldest tuple is left out, which
    breaks the guarantee that a window covers exactly its last ``ws``
    tuples."""
    return each_batch(functools.partial(_answer, control=True), pool,
                      pushes, query)


def bytes_per_push(config: dict, traffic: dict) -> int:
    """A row per window, a lane per tuple of the window."""
    n = traffic["push_tuples"]
    w = config["query"]["window"]
    rows = (n - w["ws"]) // w["wa"] + 1 if n >= w["ws"] else 0
    return push_bytes(config, n, rows, w["ws"])
