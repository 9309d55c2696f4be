"""The closed loop of a stateful stream: as ``closed.py``, one client
keeping ``in_flight`` pushes submitted, but pushes are numbered by their
place in the stream, warm-up included (the harness's ``warmup_pushes``
came first), so that the reference can replay the stream up to a sampled
push, and push ``p`` carries the pool's batch ``p % len(batches)``, as in
warm-up.  The sample is drawn uniformly among the pushes whose results
hold a window (``num_groups`` above 0 in some row); the window stays open
past ``seconds`` until one such push is ready (at most ``GRACE_S``
longer), so that every run compares one."""
from __future__ import annotations

import collections
import time

import numpy as np

import harness
import trace_reduce

#: how long past ``seconds`` the loop waits for a push that emits
GRACE_S = 60.0


def emits(out) -> bool:
    return bool(np.asarray(out.num_groups).max() > 0)


def measure(call, batches, seconds: float, traffic: dict, rng,
            annotate: bool) -> harness.Window:
    import jax

    in_flight = traffic["in_flight"]
    if in_flight < 1:
        raise harness.SetupError("in_flight must be at least 1")
    if annotate:
        from jax.profiler import TraceAnnotation
    size, kept, offered = traffic["check_sample"], [], 0
    first = traffic["warmup_pushes"]
    lat, pending = [], collections.deque()
    i = 0
    start = time.perf_counter()
    deadline = start + seconds
    end = start
    while True:
        while len(pending) < in_flight and (
                i == 0 or time.perf_counter() < deadline
                or (not kept and time.perf_counter() < deadline + GRACE_S)):
            batch = batches[(first + i) % len(batches)]
            t0 = time.perf_counter()
            if annotate:
                with TraceAnnotation(trace_reduce.PUSH_SPAN):
                    out = call(*batch)
            else:
                out = call(*batch)
            pending.append((first + i, t0, out))
            i += 1
        if not pending:
            break
        j, t0, out = pending.popleft()
        if annotate:
            with TraceAnnotation(trace_reduce.WAIT_SPAN):
                jax.block_until_ready(out)
        else:
            jax.block_until_ready(out)
        end = time.perf_counter()
        lat.append(end - t0)
        if emits(out):
            if len(kept) < size:
                kept.append((j, out))
            else:
                r = int(rng.integers(0, offered + 1))
                if r < size:
                    kept[r] = (j, out)
            offered += 1
    return harness.Window(latencies=lat, seconds=end - start, sample=kept)
