"""Record the small TPU traces that ``test_trace_reduce.py`` reads.  Run
on one chip:

    python3 bench/tests/record_trace.py

For each cell, cut as ``tiny.TINY`` says, it plans and compiles the
timed call as a run does, warms it up, traces a few pushes with the
benchmark's own spans, and writes ``bench/tests/data/<cell>.xplane.pb``
and ``<cell>.json`` (the kernel names and the push count).  The trace
keeps the source location of every op; the checkout's path in them is
overwritten with a placeholder of the same length (protobuf strings are
length-prefixed, so the file stays valid) so that the file names no
machine's directories.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
sys.path[:0] = [str(TESTS), str(ROOT / "bench"), str(ROOT / "src")]

import gen  # noqa: E402
import tiny  # noqa: E402
import trace_reduce  # noqa: E402

DATA = TESTS / "data"
#: traced seconds per cell: a handful of pushes, a small file
SECONDS = {"enthuse.flat-swag": 0.01, "enthuse.per-group": 0.0}


def main() -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    DATA.mkdir(exist_ok=True)
    for name, seconds in SECONDS.items():
        cell = tiny.tiny_cell(name)
        batches = gen.batches(cell.config, cell.traffic, 7)
        system = cell.entry().build(cell, batches, True)
        call, kernels = system.call, system.kernels
        jax.block_until_ready(call(*batches[0]))
        tmp = Path(tempfile.mkdtemp(prefix="bench-record-"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp), profiler_options=opts):
            lat = cell.loop().measure(
                call, batches, seconds, dict(cell.traffic, check_sample=0),
                np.random.default_rng(0), True).latencies
        raw = Path(trace_reduce.find_xplane(tmp)).read_bytes()
        root = str(ROOT).encode()
        (DATA / f"{name}.xplane.pb").write_bytes(
            raw.replace(root, (b"/checkout" + b"_" * len(root))[:len(root)]))
        shutil.rmtree(tmp, ignore_errors=True)
        (DATA / f"{name}.json").write_text(json.dumps(
            {"pushes": len(lat), "kernels": kernels}, indent=1) + "\n")
        print(name, len(lat), "pushes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
