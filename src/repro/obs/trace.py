"""Stage scopes and host spans, both on the profiler's clock.

Device stages.  ``stage(name)`` is a ``jax.named_scope("repro.<name>")``
around one stage of a jitted engine path::

    with trace.stage("dir_scan"):
        carry, snaps = jax.lax.scan(chunk, init, gc)

The scope lands in the ``op_name`` metadata of every HLO instruction the
stage lowers to, so a profiler trace's device ops map back to stages by
instruction (the innermost ``repro.<stage>`` segment wins).  It is
metadata only: the compiled program, stripped of metadata, is the same
with or without it.  :data:`STAGES` is the one vocabulary; an unknown name
raises.

Host spans.  ``span(name, **args)`` is a
``jax.profiler.TraceAnnotation("repro.<name>", **args)`` around host work
(``plan``, ``dispatch``, the sharded ``partition``/``local``/``merge``/
``finalize``), visible in a profiler trace beside the device's ops::

    with trace.span("dispatch", backend=p.backend, path=p.path):
        res = run(...)

Inside a :func:`capture` block each span also records its host duration.
Nothing syncs the device: a span around asynchronous dispatch measures the
dispatch; device time per stage comes from the profiler's trace.  Under
``jax.jit`` the spans run at trace time only.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Iterator, List, Optional

import jax

PREFIX = "repro."

#: stage name -> (what it covers, the benchmark metric that reads it)
STAGES = {
    "frame": ("the stream cut into WA-wide panes (frame_panes)", None),
    "sort_panes": ("each pane sorted once, _sort_panes_kernel",
                   "sort_ms_per_push"),
    "pane_merge": ("each window merged from its presorted panes, then the "
                   "op tails, _pane_kernel", "pane_merge_ms_per_push"),
    "window_sort": ("each window re-sorted whole, then the op tails "
                    "(swag_pallas)", None),
    "dir_scan": ("the pane-store directory of pergroup_write_plan: ranks "
                 "by a stable group sort, one loop step per pane "
                 "allocation, snapshots by slot; the per-tuple scan when "
                 "the push's panes do not fit",
                 "dir_scan_ms_per_push.per_group"),
    "dir_snapshot": ("per-chunk directory snapshots after the scan: written "
                     "slots, close-sort mask, staleness bounds, unique "
                     "groups", None),
    "slot_partials": ("per-slot partial aggregates, _pergroup_fused_kernel",
                      None),
    "slot_fold": ("slot partials folded into per-group values "
                  "(_combine_slot_partials)",
                  "slot_fold_ms_per_push.per_group"),
    "store_push": ("merge-replay path: pane-store push and run gather per "
                   "chunk (per_group_chunk_scan)", None),
    "replay": ("merge-replay path: merge and op tails over the gathered "
               "runs", None),
    "reorder": ("the event-time reorder buffer: the scan of "
                "_reorder_cycle over a push, then the drain", None),
    "assemble": ("valid mask and padded outputs", None),
    "time_partials": ("the pane-partial table's push: lateness by a "
                      "cumulative max, pane ids, the sort by (pane, group) "
                      "and one partial row per run",
                      "time_partials_ms_per_push"),
    "table_merge": ("the push's partial rows merged into the sorted "
                    "pane-partial table by rank, retired panes dropped",
                    "table_merge_ms_per_push"),
    "window_emit": ("closed hopping windows: each window's panes merged by "
                    "group, folded and compacted into a row",
                    "window_emit_ms_per_push"),
}


def stage(name: str):
    """``jax.named_scope`` of the engine stage ``name`` (from
    :data:`STAGES`)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are "
                         f"{sorted(STAGES)}")
    return jax.named_scope(PREFIX + name)


@dataclasses.dataclass
class Span:
    name: str
    depth: int
    start_s: float
    duration_s: float = 0.0
    args: dict = dataclasses.field(default_factory=dict)

    def label(self) -> str:
        extra = ",".join(f"{k}={v}" for k, v in self.args.items())
        return f"{self.name}[{extra}]" if extra else self.name

    def to_dict(self) -> dict:
        return {"name": self.name, "depth": self.depth,
                "start_s": self.start_s, "duration_s": self.duration_s,
                "args": dict(self.args)}


class Tracer:
    """Collects completed spans for one :func:`capture` block."""

    def __init__(self) -> None:
        self.spans: List[Span] = []
        self._depth = 0

    def report(self) -> str:
        return "\n".join(f"{'  ' * s.depth}{s.label()}: "
                         f"{s.duration_s * 1e3:.3f} ms" for s in self.spans)

    def to_dicts(self) -> list:
        return [s.to_dict() for s in self.spans]

    def durations(self) -> dict:
        """name -> summed duration in seconds (over all spans of that name)."""
        out: dict = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out


_ACTIVE: List[Tracer] = []


@contextmanager
def capture() -> Iterator[Tracer]:
    """Activate a tracer; spans entered inside the block are recorded."""
    tracer = Tracer()
    _ACTIVE.append(tracer)
    try:
        yield tracer
    finally:
        _ACTIVE.remove(tracer)


class _LiveSpan:
    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: Tracer, name: str, args: dict) -> None:
        self._tracer = tracer
        self._span = Span(name, tracer._depth, 0.0, args=args)
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + name,
                                                        **args)

    def __enter__(self) -> "_LiveSpan":
        self._annotation.__enter__()
        self._span.depth = self._tracer._depth
        self._tracer._depth += 1
        self._span.start_s = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._span.duration_s = time.perf_counter() - self._span.start_s
        self._tracer._depth -= 1
        self._tracer.spans.append(self._span)
        self._annotation.__exit__(*exc)
        return False


def span(name: str, **args):
    """A host span ``repro.<name>`` on the profiler's clock, recorded by
    the active tracer if there is one."""
    if not _ACTIVE:
        return jax.profiler.TraceAnnotation(PREFIX + name, **args)
    return _LiveSpan(_ACTIVE[-1], name, args)


def active() -> Optional[Tracer]:
    """The innermost active tracer, or None."""
    return _ACTIVE[-1] if _ACTIVE else None
