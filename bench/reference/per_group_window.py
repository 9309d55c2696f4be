"""Per-group count windows held without eviction.

The stream is cut into chunks of ``wa`` tuples; after chunk ``e`` (the
first ``(e+1)*wa`` tuples) every group seen so far is answered over its
own last ``ws_per_group`` tuples, with each op of their keys.  Rows are
evaluations, groups ascending; each row has ``capacity`` lanes.  The
store is sized so that no group's window is ever cut short, so these
answers are exact; a configuration whose capacity could evict is refused.
Each push is answered on its own (the query holds no state between
pushes).
"""
from __future__ import annotations

import functools

import numpy as np

from reference.layout import each_batch, push_bytes, reduce_ranges, \
    scatter_rows


def _answer(batch, query, *, pane_quantised: bool):
    groups, keys = batch["groups"], batch["keys"]
    w = query["window"]
    ws, wa, cap = w["ws_per_group"], w["wa"], w["capacity"]
    if not isinstance(ws, int):
        raise ValueError("this reference serves one window size for every "
                         "group")
    ne = groups.size // wa
    n = ne * wa
    uniq, gi = np.unique(groups[:n].astype(np.int64), return_inverse=True)
    if uniq.size * (-(-ws // wa) + 1) > cap:
        raise ValueError(f"{uniq.size} groups need {-(-ws // wa) + 1} pane "
                         f"slots each, more than capacity {cap}: the store "
                         f"would evict, which this reference does not model")
    hist = np.zeros((ne, uniq.size), np.int64)
    np.add.at(hist, (np.arange(n) // wa, gi), 1)
    seen = np.cumsum(hist, axis=0)          # [ne, groups] tuples seen
    lo = np.maximum(seen - ws, 0)           # window start, own-tuple rank
    if pane_quantised:
        lo = -(-lo // wa) * wa
    order = np.argsort(gi, kind="stable")   # group-major, arrival order
    sk = keys[:n][order]
    start = np.concatenate([[0], np.cumsum(hist.sum(axis=0))[:-1]])
    present = seen > 0
    row, col = np.nonzero(present)          # row-major: groups ascending
    lane = np.cumsum(present, axis=1)[row, col] - 1
    a = start[col] + lo[row, col]
    b = start[col] + seen[row, col]
    vals = reduce_ranges(sk, a, b, query["ops"])
    return scatter_rows(ne, cap, row, lane, uniq[col], vals)


def evaluate(pool, pushes, query) -> dict:
    return each_batch(functools.partial(_answer, pane_quantised=False),
                      pool, pushes, query)


def control(pool, pushes, query) -> dict:
    """Windows cut back to each group's whole panes: the tuples of the
    oldest, partly expired pane are left out, as a store one slot short
    per group would evict them.  Breaks the guarantee that no window is
    cut short."""
    return each_batch(functools.partial(_answer, pane_quantised=True),
                      pool, pushes, query)


def bytes_per_push(config: dict, traffic: dict) -> int:
    """A row per evaluation, a lane per pane slot."""
    n = traffic["push_tuples"]
    w = config["query"]["window"]
    return push_bytes(config, n, n // w["wa"], w["capacity"])
