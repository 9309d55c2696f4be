"""Run one benchmark cell once on the chip(s) this machine holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``bench/manifest.py``).  The run makes its data
from ``--seed``, plans and compiles once (set-up), measures for
``--seconds`` with the profiler off (``--trace 0``: the end-to-end
metrics) or on (``--trace 1``: the per-layer metrics, read from the
profiler's trace), then checks a sample of the window's results against
the plain reference.  Its last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each number compared
beside its limit; the same numbers close standard error.

It exits non-zero without that line when JAX finds no TPU or fewer chips
than the cell asks for, or when the system under test (``src/repro``) is
not beside this directory.  JAX's persistent compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else in ``.jax_cache/``
at the root of the checkout, a fixed path, so that only a cell's first
run in a checkout compiles and two checkouts share nothing.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import manifest

    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the system under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness
    try:
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS)
    except harness.SetupError as e:
        print(f"bench/run.py: {args.workload}: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
