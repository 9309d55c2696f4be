"""Read the two numbers each limit of ``bench/check.py`` is set from, on the
chip, at the cell's own size, in one process:

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 [--seconds 5]

For each seed it makes that seed's data, drives the timed call (compiled
once, as in a run) for a short window at the cell's own load, and checks
the same sample a run checks: the mismatches are the program's reading.
Then it puts the configuration's control (the plain reference with one of
its guarantees broken) in the program's place on the same batches: those
mismatches are the control's reading.  One JSON line per seed, then a
summary with the program's largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    import numpy as np

    import check
    import gen
    import manifest

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    ref, loop = cell.reference(), cell.loop()
    query = cell.config["query"]
    names = gen.column_names(cell.config)
    system = None
    program, control = [], []
    for seed in seeds:
        batches = gen.batches(cell.config, cell.traffic, seed)
        if system is None:
            system = cell.entry().build(cell, batches, True)
        jax.block_until_ready(system.call(*batches[0]))
        rng = np.random.default_rng([seed % 2 ** 63, 1])
        win = loop.measure(system.call, batches, args.seconds, cell.traffic,
                           rng, False)
        got = [(i, system.to_host(out)) for i, out in win.sample]
        pushes = len(win.latencies)
        del win
        pool = [dict(zip(names, (np.asarray(x) for x in bt)))
                for bt in batches]
        del batches
        t0 = time.perf_counter()
        verdict = check.compare_sample(ref, query, pool, got, 0)
        ref_s = time.perf_counter() - t0
        sampled = [i for i, _ in got]
        want = ref.evaluate(pool, sampled, query)
        ctl = ref.control(pool, sampled, query)
        program.append(verdict.checks["mismatched_elements"]["value"])
        control.append(sum(check.mismatches(ctl[i], want[i])
                           for i in sampled))
        print(json.dumps({"seed": seed, "pushes": pushes,
                          "compared": len(got),
                          "program_mismatches": program[-1],
                          "control_mismatches": control[-1],
                          "reference_s": ref_s}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "program_max": max(program),
                      "control_min": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
