"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  complexity_table    -> paper Table I (entity model + fused-vs-modular HLO)
  speedup_groupby     -> paper §IV speedup protocol (distribution sweep)
  swag_bench          -> paper §V / Fig. 4 SWAG throughput (incl. median,
                         re-sort baseline vs pane path, plus
                         swag_per_group/* rows: per-group windows on the
                         shared pane store, num_groups x WS_g)
  query_overhead      -> repro.query planner+dispatch cost vs direct calls
                         + fused multi-op vs per-op (sort-once asserted)
  eventtime_bench     -> time-range windows (Window(range=..., slide=...)):
                         per-window replay vs the flip-batched two-stack,
                         plus reorder-buffer ingest throughput
  sort_bench          -> sorter substrate (FLiMS role)
  moe_dispatch_bench  -> beyond-paper: engine-as-MoE-dispatch vs one-hot
                         (quarantined: runs only via --only, never in the
                         default sweep)
  shard_scaling       -> two-phase mergeable-state execution over 1/2/4/8
                         virtual CPU devices in a subprocess child
                         (quarantined: the child cannot get a chip the
                         parent already holds, so it refuses to run off
                         the CPU; one-combine-tree asserted)

``swag_bench``, ``query_overhead``, ``shard_scaling`` and
``eventtime_bench`` rows additionally are merged into ``BENCH_swag.json``
at the repo root — machine-readable (name, us_per_call, tuples_per_s) so the SWAG
perf + dispatch-overhead + shard-scaling + event-time trajectory is tracked
across PRs.

Rows that carry ``engine_stats`` (collect_stats=True counters attached by
the module) additionally land in ``BENCH_stats.jsonl`` together with the
process-global MetricsRegistry snapshot — the observability sidecar the
measured-cost router will consume.

``--only PREFIX`` runs the matching module(s) alone and merges their rows
into the tracked json in place.  PREFIX first matches module names; when no
module matches, it falls back to *row-name* prefixes declared by modules via
``ROW_PREFIXES`` (e.g. ``--only swag_per_group`` runs just the per-group
rows of ``swag_bench``), and only the matching rows are re-measured/merged.

JAX's compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
else to ``.jax_cache/`` at the repo root.
"""
from __future__ import annotations

import json
import pathlib
import sys

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

#: modules whose rows feed the tracked BENCH_swag.json
_JSON_MODULES = ("swag_bench", "query_overhead", "shard_scaling",
                 "eventtime_bench")


def _json_row(r: dict) -> dict:
    row = {"name": r["name"],
           "us_per_call": r["us_per_call"],
           "tuples_per_s": r["tuples_per_s"]}
    if "engine_stats" in r:
        row["engine_stats"] = r["engine_stats"]
    return row


def _write_stats_jsonl(rows: list[dict]) -> None:
    """Observability sidecar: every row that carries ``engine_stats``
    lands in ``BENCH_stats.jsonl`` (one JSON object per line), followed
    by the process-global :class:`~repro.obs.registry.MetricsRegistry`
    snapshot — the observed (backend, plan) -> tuples/s cells the
    measured-cost router will consume."""
    from repro.obs import export as _export
    from repro.obs import registry as _registry

    records = [{"kind": "bench_row", **_json_row(r)}
               for r in rows if "engine_stats" in r]
    for (backend, plan), cell in _registry.get_registry().snapshot().items():
        records.append({"kind": "observed_throughput", "backend": backend,
                        "plan": plan, **cell})
    if not records:
        return
    out = _REPO_ROOT / "BENCH_stats.jsonl"
    _export.write_jsonl(records, out)
    print(f"# wrote {out}", file=sys.stderr, flush=True)


def _use_compile_cache() -> None:
    """Entry-point setting: keep JAX's persistent compile cache at a fixed
    path (``$JAX_COMPILATION_CACHE_DIR`` wins when set)."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_REPO_ROOT / ".jax_cache"))


def main() -> None:
    import argparse

    _use_compile_cache()
    from benchmarks import (complexity_table, eventtime_bench,
                            moe_dispatch_bench, query_overhead,
                            shard_scaling, sort_bench, speedup_groupby,
                            swag_bench)
    modules = [
        ("complexity_table", complexity_table),
        ("speedup_groupby", speedup_groupby),
        ("swag_bench", swag_bench),
        ("query_overhead", query_overhead),
        ("eventtime_bench", eventtime_bench),
        ("sort_bench", sort_bench),
    ]
    # explicit --only opt-in, never part of the default sweep: the
    # beyond-paper demo is long-running, and shard_scaling re-executes
    # itself on virtual CPU devices after this process has taken the device
    quarantined = [("moe_dispatch_bench", moe_dispatch_bench),
                   ("shard_scaling", shard_scaling)]

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", metavar="PREFIX", default=None,
                    help="run only modules whose name starts with PREFIX; "
                         "their BENCH_swag.json rows are merged in place "
                         "(other modules' rows are kept)")
    # positional module name kept for backward compatibility with
    # `python -m benchmarks.run swag_bench`
    ap.add_argument("module", nargs="?", default=None)
    args = ap.parse_args()
    only = args.only if args.only is not None else args.module
    row_only = None
    if only:
        modules += quarantined
        by_name = [(n, m) for n, m in modules if n.startswith(only)]
        if by_name:
            modules = by_name
        else:
            # fall back to row-name prefixes: run just the module(s) that
            # emit matching rows, and just those rows
            modules = [(n, m) for n, m in modules
                       if any(rp.startswith(only)
                              for rp in getattr(m, "ROW_PREFIXES", ()))]
            if not modules:
                ap.error(f"no benchmark module matches prefix {only!r}")
            row_only = only

    print("name,us_per_call,derived")
    json_rows: list[dict] = []
    ran = []
    for name, mod in modules:
        rows = mod.run(only=row_only) if row_only else mod.run()
        for row in rows:
            print(f"{row['name']},{row['us_per_call']},{row['derived']}",
                  flush=True)
        if name in _JSON_MODULES:
            json_rows.extend(rows)
            ran.append(name)
    # merge, never rewrite: rows of modules that did not run (a partial
    # invocation, or the quarantined shard_scaling) are kept
    if ran:
        _merge_swag_json(json_rows)
        _write_stats_jsonl(json_rows)


def _merge_swag_json(rows: list[dict]) -> None:
    out = _REPO_ROOT / "BENCH_swag.json"
    existing = []
    if out.exists():
        existing = json.loads(out.read_text())
    new_names = {r["name"] for r in rows}
    payload = [e for e in existing if e["name"] not in new_names]
    payload += [_json_row(r) for r in rows if "tuples_per_s" in r]
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# merged into {out}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
