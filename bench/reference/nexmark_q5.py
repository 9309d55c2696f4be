"""NEXmark Query 5's first stage: bids per auction over hopping windows of
event time, each window emitted once when the watermark passes its end.

The bid stream, as ``bench/configs/nexmark-q5.json`` describes it: bid
``i`` of the whole stream (counted across pushes) is made from lane ``l``
of push ``p`` (``i = p * N + l``, ``N`` bids a push, push ``p`` carrying
pool batch ``p % len(pool)``) and that batch's draws ``hot``, ``delay``
and ``price`` at lane ``l``:

* stamp ``i // 1000`` ms, less ``delay`` when ``delay >= 0`` (about 10%
  of bids, up to 3000 ms), never below 0;
* auction: with ``last = 3 * i // 46``, the hot auction ``(last // 100) *
  100`` when ``hot < 0``, else ``max(last - 100, 0) + hot``;
* price: ``price`` (count does not read it).

A bid is late, and counted nowhere, iff its stamp is more than
``max_lateness`` below the largest stamp of the bids up to and including
it.  After each push the watermark is the largest stamp so far less
``max_lateness``; window ``[e - range, e)`` (``e`` a multiple of
``slide``) is emitted by the first push whose watermark is at or past
``e``, at most ``ceil(range / slide)`` windows a push, oldest first, the
rest waiting.  The stream has a stamp in every millisecond from 0 on, so
every window from the first, ``[slide - range, slide)``, holds a bid.

A push's answer has one row per window it may emit, ``capacity`` lanes
each: the auctions with a bid in the window ascending, each with its bid
count; rows beyond the push's windows are empty.  Only what the sampled
pushes need is replayed: every push's largest stamp (for the watermarks)
and the bids of the pushes that can hold a sampled window's bids.
Arithmetic is int64 throughout.  Imports nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

from reference.layout import scatter_rows

#: bids per millisecond of event time
BIDS_PER_MS = 1000
#: person : auction : bid events are 1 : 3 : 46 (Beam's Nexmark)
AUCTIONS, BIDS = 3, 46
#: auctions in flight, and the span of the cold auction draw
IN_FLIGHT, COLD_SPAN = 100, 111


def _rows(query) -> int:
    w = query["window"]
    return -(-w["range"] // w["slide"])


def _bids(batch, push: int, n: int):
    """Stamps and auctions of one push's bids (int64)."""
    i = push * n + np.arange(n, dtype=np.int64)
    ts = i // BIDS_PER_MS
    d = batch["delay"].astype(np.int64)
    ts = np.maximum(np.where(d >= 0, ts - d, ts), 0)
    last = AUCTIONS * i // BIDS
    h = batch["hot"].astype(np.int64)
    auction = np.where(h < 0, last // IN_FLIGHT * IN_FLIGHT,
                       np.maximum(last - IN_FLIGHT, 0) + h)
    return ts, auction


def _push_max(pool, pushes: int, n: int) -> np.ndarray:
    """The largest stamp of each push ``0 .. pushes - 1``.  A delayed bid
    before a push's last undelayed one stamps no later than it, so only
    the lanes from there on are read."""
    out = np.empty(pushes, np.int64)
    tails = []
    for b in pool:
        d = b["delay"].astype(np.int64)
        undelayed = np.flatnonzero(d < 0)
        start = int(undelayed[-1]) if undelayed.size else 0
        tails.append((np.arange(start, n, dtype=np.int64), d[start:]))
    for p in range(pushes):
        lanes, d = tails[p % len(pool)]
        ts = (p * n + lanes) // BIDS_PER_MS
        out[p] = np.maximum(np.where(d >= 0, ts - d, ts), 0).max()
    return out


def _schedule(query, stamps: np.ndarray) -> dict:
    """``{push: [window end, ...]}``: the windows each push emits."""
    w = query["window"]
    slide, late = w["slide"], w["max_lateness"]
    rows = _rows(query)
    wm = np.maximum.accumulate(stamps) - late
    nxt = slide                     # the first window holding stamp 0
    out = {}
    for p, m in enumerate(wm.tolist()):
        ends = []
        while nxt <= m and len(ends) < rows:
            ends.append(nxt)
            nxt += slide
        out[p] = ends
    return out


def _window_counts(pool, query, end: int, upto: int, n: int,
                   stamps: np.ndarray, shift: int):
    """Auctions and bid counts over ``[end - range + shift, end + shift)``
    from the bids of pushes ``<= upto``."""
    w = query["window"]
    lo_t, hi_t = end - w["range"] + shift, end + shift
    # a bid stamps at most max_lateness before i // BIDS_PER_MS
    first = max(lo_t * BIDS_PER_MS // n, 0)
    last = min((hi_t + w["max_lateness"] + 1) * BIDS_PER_MS // n, upto)
    auctions = []
    seen = stamps[first - 1] if first > 0 else np.iinfo(np.int64).min
    for p in range(first, last + 1):
        ts, auction = _bids(pool[p % len(pool)], p, n)
        run = np.maximum(np.maximum.accumulate(ts), seen)
        ok = (ts >= run - w["max_lateness"]) & (ts >= lo_t) & (ts < hi_t)
        auctions.append(auction[ok])
        seen = max(seen, int(ts.max()))
    return np.unique(np.concatenate(auctions), return_counts=True)


def _answer(pool, pushes, query, shift: int) -> dict:
    if query["ops"] != ["count"]:
        raise ValueError("this reference counts bids per auction only")
    n = pool[0]["delay"].size
    cap = query["window"]["capacity"]
    rows = _rows(query)
    top = max(pushes) + 1
    stamps = np.maximum.accumulate(_push_max(pool, top, n))
    schedule = _schedule(query, stamps)
    out = {}
    for p in pushes:
        row, lane, group, count = [], [], [], []
        for r, end in enumerate(schedule[p]):
            a, c = _window_counts(pool, query, end, p, n, stamps, shift)
            row.append(np.full(a.size, r))
            lane.append(np.arange(a.size))
            group.append(a)
            count.append(c)
        cat = (lambda xs: np.concatenate(xs) if xs
               else np.zeros(0, np.int64))
        out[p] = scatter_rows(rows, cap, cat(row), cat(lane), cat(group),
                              {"count": cat(count)})
    return out


def evaluate(pool, pushes, query) -> dict:
    """``{push: answer}`` for the stream's pushes ``pushes`` (numbered
    from the stream's start, warm-up included)."""
    return _answer(pool, pushes, query, shift=0)


def control(pool, pushes, query) -> dict:
    """Every window shifted 1 ms later: off by the bids of one millisecond
    at each end."""
    return _answer(pool, pushes, query, shift=1)


def bytes_per_push(config: dict, traffic: dict) -> float:
    """The push's bids read once (every column) and its share of the
    emitted window rows written once: a window row is ``capacity`` lanes
    of group, validity and count, plus its group count."""
    n = traffic["push_tuples"]
    w = config["query"]["window"]
    inputs = n * sum(np.dtype(c.get("dtype", "int32")).itemsize
                     for c in config["data"]["columns"])
    rows = n / BIDS_PER_MS / w["slide"]
    return inputs + rows * (w["capacity"] * (4 + 1 + 4) + 4)

