"""Pure-Python oracle of closed hopping event-time windows, shared by the
streaming event-time tests.

Window ``[e - R, e)``, with ``e`` a multiple of ``S``, is emitted once, by
the first push whose watermark is at or past ``e``, if it holds an
accepted tuple; a push emits at most ``rows`` windows, oldest first, and
later ones wait for the next push.  ``flush`` emits every window left.
"""
from __future__ import annotations

import numpy as np

TS_MIN = -(2 ** 30)
PAD_GROUP = 2 ** 31 - 1


def py_value(op, vals):
    if op == "mean":
        return float(np.float32(sum(vals)) / np.float32(len(vals)))
    return {"min": min, "max": max, "sum": sum, "count": len}[op](vals)


def accept(pushes, lateness):
    """The single-device lateness rule over ``pushes`` (lists of stamps in
    arrival order): a tuple is late iff it is more than ``lateness`` below
    the largest stamp seen up to and including it.  Returns each push's
    accepted mask and the watermark after it."""
    run = TS_MIN
    masks, wms = [], []
    for ts in pushes:
        m = []
        for t in np.asarray(ts).tolist():
            run = max(run, t)
            m.append(t >= run - lateness)
        masks.append(np.array(m, bool))
        wms.append(run - lateness)
    return masks, wms


def window_rows(g, k, t, e, time_range, ops):
    """{group: (op values...)} over the accepted tuples in [e - R, e)."""
    buckets: dict[int, list[int]] = {}
    for gi, ki, ti in zip(g, k, t):
        if e - time_range <= ti < e:
            buckets.setdefault(int(gi), []).append(int(ki))
    return {gi: tuple(py_value(op, v) for op in ops)
            for gi, v in sorted(buckets.items())}


class ClosedWindows:
    """Feed pushes of accepted tuples with each push's watermark; get the
    windows each push emits as ``[(e, {group: values})]``."""

    def __init__(self, time_range, slide, rows, ops):
        self.r, self.s, self.rows, self.ops = time_range, slide, rows, ops
        self.g, self.k, self.t = [], [], []
        self.done = set()

    def _pending(self, wm):
        ends = set()
        for ti in self.t:
            e = (ti // self.s + 1) * self.s
            while e - self.r <= ti:
                ends.add(e)
                e += self.s
        return sorted(e for e in ends if e <= wm and e not in self.done)

    def _emit(self, ends):
        self.done.update(ends)
        return [(e, window_rows(self.g, self.k, self.t, e, self.r, self.ops))
                for e in ends]

    def push(self, g, k, t, wm):
        self.g += list(g)
        self.k += list(k)
        self.t += list(np.asarray(t).tolist())
        return self._emit(self._pending(wm)[:self.rows])

    def flush(self):
        return self._emit(self._pending(float("inf")))


def got_rows(res, ops):
    """``[(e, {group: values})]`` from an event-time result (ports tuple,
    ``AggResult`` or ``StreamResult``): one entry per row holding a
    window."""
    if isinstance(res, tuple) and not hasattr(res, "_fields"):
        g, values, valid, num, _rr, ends = res
    else:
        g, values, valid, num = res.groups, res.values, res.valid, \
            res.num_groups
        ends = res.window_ends
    if not isinstance(values, dict):
        values = {ops[0]: values}
    g, valid, num, ends = (np.asarray(x) for x in (g, valid, num, ends))
    vals = {op: np.asarray(values[op]) for op in ops}
    out = []
    for r in range(g.shape[0]):
        if num[r] == 0:
            assert not valid[r].any() and ends[r] == TS_MIN
            continue
        n = int(num[r])
        assert valid[r, :n].all() and not valid[r, n:].any()
        assert (g[r, n:] == PAD_GROUP).all()
        assert list(g[r, :n]) == sorted(g[r, :n])
        out.append((int(ends[r]), {
            int(g[r, j]): tuple(
                float(vals[op][r, j]) if op == "mean" else int(vals[op][r, j])
                for op in ops) for j in range(n)}))
    return out
