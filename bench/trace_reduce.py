"""From the profiler's trace (``.xplane.pb``) to per-layer numbers.

What the trace holds on a TPU, and what is read from it:

* plane ``/device:TPU:<i>``, line ``XLA Ops``: one event per executed HLO
  instruction, named by the instruction's HLO text.  Ops inside a loop
  body are nested inside the loop's own event, so each event's *self*
  time (its duration less that of the events nested in it) is what it
  adds.  A Pallas kernel is an op whose HLO is a ``tpu_custom_call``;
  every other op is XLA's own.
* plane ``/host:CPU``: the benchmark's spans ``bench.push`` (the call
  into the system until it returns) and ``bench.wait`` (blocked until the
  results are ready), on the same clock.

The traced window runs from the first push's start to the last push's
results.  Device busy time is the union of the top-level op intervals in
it; the idle share is the rest.  Where the profiler dropped events
(``Trace Buffers Dropped``), only the pushes whose results were ready
before the first drop are read.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

PUSH_SPAN = "bench.push"
WAIT_SPAN = "bench.wait"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DROPPED = "Trace Buffers Dropped"
OPS_LINE = "XLA Ops"

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction(hlo_text: str) -> str:
    """``%name = ...`` -> ``name``."""
    m = _INSTR.match(hlo_text)
    return m.group(1) if m else hlo_text[:64]


_LOWERED_CALL = re.compile(
    r'stablehlo\.custom_call @tpu_custom_call\(.*kernel_name = "([^"]+)"'
    r'.*->\s*\(?([^)\n]*)\)?\s*$')
_COMPILED_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(\(.*?\)|\S+)\s+custom-call\(')


def _dims(types: str) -> tuple:
    """Result shapes of a lowered (``tensor<4x1x8xi32>``) or compiled
    (``s32[4,1,8]{...}``) signature, as tuples of dims."""
    out = [tuple(int(d) for d in t.split("x")[:-1])
           for t in re.findall(r"tensor<([^>]*)>", types)]
    out += [tuple(int(d) for d in t.split(",") if d)
            for t in re.findall(r"[a-z]+\d*\[([\d,]*)\]", types)]
    return tuple(out)


def kernel_names(lowered_text: str, compiled_text: str) -> dict:
    """``{compiled instruction: Pallas kernel name}`` for every kernel of
    the program, matched by result shapes (kernels of one shape share a
    joined name)."""
    by_shape = defaultdict(set)
    for line in lowered_text.splitlines():
        m = _LOWERED_CALL.search(line)
        if m:
            by_shape[_dims(m.group(2))].add(m.group(1))
    out = {}
    for line in compiled_text.splitlines():
        if KERNEL_MARK not in line:
            continue
        m = _COMPILED_CALL.match(line)
        if m:
            names = by_shape.get(_dims(m.group(2)), {"pallas"})
            out[m.group(1)] = "+".join(sorted(names))
    return out


@dataclasses.dataclass
class Op:
    start: int           # ns
    end: int
    name: str            # instruction name; nested ops: "<top>/<name>"
    kernel: bool
    top: bool            # not nested in another op
    self_ns: int = 0


def _nest(events) -> list:
    """``(start, end, instruction, kernel)`` of one op line -> ``Op``s with
    self time, nested ops named under their top-level op."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    ops, stack = [], []
    for start, end, name, kernel in events:
        while stack and stack[-1].end <= start:
            stack.pop()
        op = Op(start, end, name, kernel, not stack, end - start)
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(end, parent.end) - start
            op.name = f"{stack[0].name}/{name}"
        ops.append(op)
        stack.append(op)
    return ops


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduced:
    """The traced window of one run, reduced; per-chip sums averaged over
    the chips read."""
    pushes: int
    window: tuple                 # (start ns, end ns)
    push_ns: list                 # bench.push durations
    ops: list                     # per chip: [Op] inside the window
    spans: list                   # (start, end, name) host spans
    kernel_names: dict

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, ops) -> list:
        return _union((o.start, o.end) for o in ops if o.top)

    def busy_s(self) -> float:
        return sum(sum(e - s for s, e in self._busy(ops))
                   for ops in self.ops) * 1e-9 / len(self.ops)

    def self_s(self, kernel: bool) -> float:
        return sum(sum(o.self_ns for o in ops if o.kernel == kernel)
                   for ops in self.ops) * 1e-9 / len(self.ops)

    def _label(self, op: Op) -> str:
        last = op.name.rsplit("/", 1)[-1]
        if op.kernel:
            return f"{op.name} [{self.kernel_names.get(last, 'pallas')}]"
        return op.name

    def breakdown(self) -> dict:
        """The device ops that took most self time, and the longest idle
        gaps on chip 0, each named by the benchmark span open over most
        of it (``harness`` where none was)."""
        total = defaultdict(int)
        for op in self.ops[0]:
            total[self._label(op)] += op.self_ns
        device_ops = sorted(total.items(), key=lambda kv: -kv[1])[:10]
        gaps, t = [], self.window[0]
        for s, e in self._busy(self.ops[0]) + [[self.window[1]] * 2]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        named = []
        for s, e in gaps:
            cover = defaultdict(int)
            for hs, he, name in self.spans:
                cover[name] += max(0, min(e, he) - max(s, hs))
            best = max(cover.items(), key=lambda kv: kv[1], default=None)
            name = best[0] if best and best[1] * 2 > e - s else "harness"
            named.append((name, e - s))
        named.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[n, ns * 1e-9] for n, ns in device_ops],
                "idle_gaps": [[n, ns * 1e-9] for n, ns in named[:10]]}


def _op_events(line) -> list:
    """``(start, end, instruction, kernel)`` of every event of an op line;
    the HLO text is parsed once per distinct op, as a loop body's ops
    recur once per iteration."""
    parsed = {}
    out = []
    for e in line.events:
        text = e.name
        if text not in parsed:
            parsed[text] = (instruction(text), KERNEL_MARK in text)
        start = int(e.start_ns)
        out.append((start, start + int(e.duration_ns)) + parsed[text])
    return out


def _dropped_marker(pd) -> int | None:
    """When the profiler first dropped device events, if it did."""
    starts = [int(e.start_ns) for plane in pd.planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines for e in line.events
              if line.name != OPS_LINE and e.name == DROPPED]
    return min(starts, default=None)


def load(path: str, chips: int = 1, kernel_names_: dict | None = None
         ) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, device_lines = [], {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            spans += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                       e.name) for line in plane.lines for e in line.events
                      if e.name in (PUSH_SPAN, WAIT_SPAN)]
        elif plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE and chip < chips:
                    device_lines[chip] = _op_events(line)
    if len(device_lines) < chips:
        raise ValueError(f"the trace holds ops of {len(device_lines)} of "
                         f"{chips} chips")
    dropped = _dropped_marker(pd)
    spans.sort()
    pushes = [s for s in spans if s[2] == PUSH_SPAN]
    waits = [s for s in spans if s[2] == WAIT_SPAN]
    done = [(p, w) for p, w in zip(pushes, waits)
            if dropped is None or w[1] <= dropped]
    if not done:
        raise ValueError("no push of the traced window was traced whole")
    window = (done[0][0][0], done[-1][1][1])
    spans = [s for s in spans if s[0] < window[1]]
    ops = [_nest([e for e in device_lines[chip]
                  if e[0] >= window[0] and e[1] <= window[1]])
           for chip in range(chips)]
    return Reduced(pushes=len(done), window=window,
                   push_ns=[p[1] - p[0] for p, _ in done], ops=ops,
                   spans=spans, kernel_names=kernel_names_ or {})
