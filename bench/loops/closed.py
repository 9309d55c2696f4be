"""The closed loop: one client keeps ``in_flight`` pushes submitted
(traffic key ``in_flight``), taking the pool's batches in turn; it waits
for the oldest, submits the next, until ``seconds`` have passed (at least
one push), then drains.  A push's latency runs from its submission to its
results being ready; the window closes when the last push is ready."""
from __future__ import annotations

import collections
import time

import harness
import trace_reduce


def measure(call, batches, seconds: float, traffic: dict, rng,
            annotate: bool) -> harness.Window:
    import jax

    in_flight = traffic["in_flight"]
    if in_flight < 1:
        raise harness.SetupError("in_flight must be at least 1")
    if annotate:
        from jax.profiler import TraceAnnotation
    sample = harness.Reservoir(traffic["check_sample"], rng)
    lat, pending = [], collections.deque()
    i = 0
    start = time.perf_counter()
    deadline = start + seconds
    end = start
    while True:
        while len(pending) < in_flight and (
                i == 0 or time.perf_counter() < deadline):
            batch = batches[i % len(batches)]
            t0 = time.perf_counter()
            if annotate:
                with TraceAnnotation(trace_reduce.PUSH_SPAN):
                    out = call(*batch)
            else:
                out = call(*batch)
            pending.append((i, t0, out))
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
        sample.offer(j, out)
    return harness.Window(latencies=lat, seconds=end - start,
                          sample=sample.kept)
