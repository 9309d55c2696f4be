"""One run of one cell: set-up, the measured window, the check, and the
result line.

Set-up makes the cell's data on the device from the seed, builds the
configuration's entry (``bench/entries/<entry>.py``: plan once, lower and
compile the timed call, from the persistent cache after a cell's first
run) and warms it up.  The window then drives that compiled call with the
traffic's loop (``bench/loops/<loop>.py``), with tracing off unless
``trace`` is set.  After the window the device's peak memory is read, a
sample of the window's results (drawn from the seed) is copied to the
host, the device state is freed, and the configuration's plain reference
checks every element of the sample.

An entry module exposes ``build(cell, batches, on_tpu) -> System``; a
loop module exposes ``measure(call, batches, seconds, traffic, rng,
annotate) -> Window``, where push ``i`` carries ``batches[i %
len(batches)]``.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import statistics
import tempfile
import time
from pathlib import Path
from typing import Callable

import numpy as np

import check
import gen
import hostwatch
import manifest
import trace_reduce


class SetupError(RuntimeError):
    """The run cannot measure this cell: no result line is printed."""


@dataclasses.dataclass
class System:
    """The system under test as set-up leaves it."""
    call: Callable       # call(*batch) -> result on the device (async)
    to_host: Callable    # result -> NumPy arrays in the reference layout
    kernels: dict        # {kernel instruction: Pallas kernel name}


@dataclasses.dataclass
class Window:
    """What a loop measured."""
    latencies: list      # seconds, one per push, in completion order
    seconds: float       # the window's length
    sample: list         # (push index, result on the device)
    extra: dict = dataclasses.field(default_factory=dict)  # loop's own


class Reservoir:
    """A uniform sample of ``size`` pushes' results, drawn with ``rng``."""

    def __init__(self, size: int, rng):
        self.size, self.rng, self.kept = size, rng, []

    def offer(self, push: int, out) -> None:
        if len(self.kept) < self.size:
            self.kept.append((push, out))
        else:
            r = int(self.rng.integers(0, push + 1))
            if r < self.size:
                self.kept[r] = (push, out)


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileCounter:
    """Counts JAX's tracing and compile events while ``on`` is set (a
    compile served from the persistent cache counts too)."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _duration: float, **_kw) -> None:
        if self.on and event.startswith("/jax/core/compile/"):
            self.count += 1


def device_info(devices) -> dict:
    """The device as JAX reports it; the peak is the fullest chip's."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_chip: bool = True, fault=None) -> dict:
    """One run; returns the result line as a dict.  ``fault`` (tests
    only) wraps the compiled call, to see the check fail."""
    import jax

    marks = {"start": t_process, "imports": time.perf_counter()}
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise SetupError(f"needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform!r} device(s)")
    devices = devices[:cell.chips]
    marks["devices"] = time.perf_counter()
    peaks = _peaks(devices[0].device_kind) if trace else None
    traffic = cell.traffic
    entry, loop = cell.entry(), cell.loop()
    batches = gen.batches(cell.config, traffic, seed)
    marks["data"] = time.perf_counter()
    system = entry.build(cell, batches, devices[0].platform == "tpu")
    marks["compile"] = time.perf_counter()
    call = fault(system.call) if fault is not None else system.call
    for i in range(traffic["warmup_pushes"]):
        jax.block_until_ready(call(*batches[i % len(batches)]))
    compiles = CompileCounter()
    marks["warmup"] = time.perf_counter()
    setup_s = marks["warmup"] - t_process
    steps = list(marks)
    log(phase="setup", setup_s=setup_s,
        **{f"{b}_s": marks[b] - marks[a] for a, b in zip(steps, steps[1:])})
    rng = np.random.default_rng([seed % 2 ** 63, 1])

    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc_clock = hostwatch.GcClock()
    host_before = hostwatch.snapshot()
    compiles.on = True
    try:
        win = loop.measure(call, batches, seconds, traffic, rng, trace)
    finally:
        compiles.on = False
        host = hostwatch.delta(host_before, hostwatch.snapshot())
        gc_clock.close()
        if trace:
            jax.profiler.stop_trace()
    lat = win.latencies
    med = statistics.median(lat)
    log(phase="window", pushes=len(lat), window_s=win.seconds,
        compiles_in_window=compiles.count, push_median_ms=med * 1e3,
        push_max_ms=max(lat) * 1e3,
        slow_pushes=sum(x > 1.5 * med for x in lat),
        gc_s=gc_clock.seconds, gc_runs=gc_clock.runs, host=host)
    device = device_info(devices)

    got = [(i, system.to_host(out)) for i, out in win.sample]
    kernels = system.kernels
    win = dataclasses.replace(win, sample=[])
    del call, system
    reference = cell.reference()
    ctx = Context(config=cell.config, traffic=traffic, reference=reference,
                  peaks=peaks, setup_s=setup_s, window=win)
    extra = {}
    if trace:
        reduced = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                    cell.chips, kernels)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = _read(cell, cell.per_layer,
                        dataclasses.replace(ctx, trace=reduced))
        device.update(busy_s=reduced.busy_s(), window_s=reduced.window_s())
        extra["breakdown"] = reduced.breakdown()
    else:
        metrics = _read(cell, cell.end_to_end, ctx)

    names = gen.column_names(cell.config)
    pool = [dict(zip(names, (np.asarray(x) for x in bt))) for bt in batches]
    del batches
    verdict = check.compare_sample(reference, cell.config["query"], pool,
                                   got, compiles.count)
    line = {"correct": verdict.correct, "attempted": len(lat),
            "failed": verdict.failed, "metrics": metrics, "device": device}
    line.update(extra)
    line["checks"] = verdict.checks
    return line


@dataclasses.dataclass(frozen=True)
class Context:
    """What a metric's reader (``bench/metrics/<metric>.py``) gets: the
    cell's configuration, traffic, reference module and device peaks,
    the run's set-up seconds and measured window, and with ``--trace 1``
    the reduced trace (else None)."""
    config: dict
    traffic: dict
    reference: object
    peaks: dict | None
    setup_s: float
    window: Window
    trace: trace_reduce.Reduced | None = None


def _read(cell: manifest.Cell, metrics: tuple, ctx: Context) -> dict:
    """Each metric's reading, in order; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _peaks(device_kind: str) -> dict:
    """The device's row of ``bench/peaks.json``; a kind missing there is
    an error, never a default."""
    table = json.loads((manifest.BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SetupError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]
