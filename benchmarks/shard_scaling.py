"""Two-phase mergeable-state execution: throughput vs host-device count.

Sweeps ``shard_scaling/*`` rows — a grouped multi-op query and a SWAG
query executed through ``execute(..., mesh=...)`` over 1 / 2 / 4 / 8
host-platform devices — and asserts the merge stage traces exactly **one
combine tree** (log2(S) vmapped pairwise-merge rounds for the engine path,
log2(P) per-window rounds for the pane path; never S-1 sequential merges).

Forcing the host-platform device count requires ``XLA_FLAGS`` to be set
before jax initialises, and every *other* benchmark must keep seeing one
device (their tracked numbers would silently change run conditions
otherwise), so :func:`run` re-executes this module as a **subprocess
child** with the flag set and collects its rows from stdout JSON — same
pattern as the multi-device tests (``tests/test_pipeline.py``).  On an
accelerator the parent already holds the device and the child cannot get
it, so both refuse to run off the CPU; ``benchmarks.run`` keeps this module
out of its default sweep (``--only shard_scaling`` runs it).

Reading the rows: host-platform "devices" are slices of ONE CPU whose
single-device XLA already uses every core, so adding fake devices only adds
partition/collective overhead — throughput *decreasing* with shards here is
the expected CPU-CI shape.  The rows track that overhead (and the
one-combine-tree property) across PRs; real scaling needs real devices.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

N = 32768
ENGINE_OPS = ("sum", "min", "count", "dc")
SWAG_OPS = ("sum", "min", "median")
WS, WA = 1024, 256
SHARDS = (1, 2, 4, 8)


def _child() -> list[dict]:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_fn
    from repro.core import engine as _engine

    _require_cpu()
    from repro.core.swag import num_windows
    from repro.obs.export import to_jsonable
    from repro.query import Query, Window, execute, plan

    assert len(jax.devices()) >= max(SHARDS), jax.devices()

    rng = np.random.default_rng(7)
    g = np.sort(rng.integers(0, 64, N)).astype(np.int32)
    k = rng.integers(0, 1000, N).astype(np.int32)
    order = np.lexsort((k, g))
    gs, ks = jnp.array(g[order]), jnp.array(k[order])   # engine contract
    gw = jnp.array(rng.integers(0, 64, N).astype(np.int32))
    kw = jnp.array(rng.integers(0, 1000, N).astype(np.int32))

    def tree_rounds(fn, *args) -> int:
        """Pairwise table merges traced by ``fn`` — one per tree round
        (vmapped nodes trace once), so 'one combine tree' == log2(S)."""
        calls = [0]
        orig = _engine.combine_partial_tables

        def counting(*a, **kw_):
            calls[0] += 1
            return orig(*a, **kw_)

        _engine.combine_partial_tables = counting
        try:
            jax.make_jaxpr(fn)(*args)
        finally:
            _engine.combine_partial_tables = orig
        return calls[0]

    rows = []
    for s in SHARDS:
        mesh = (None if s == 1 else
                jax.make_mesh((s,), ("shards",), devices=jax.devices()[:s]))

        # -- grouped multi-op ------------------------------------------------
        q = Query(ops=ENGINE_OPS)
        p = plan(q, backend="reference", num_shards=s)
        fn = jax.jit(lambda a, b, p=p, m=mesh:
                     execute(p, a, b, mesh=m)[0].values)
        if s > 1:
            rounds = tree_rounds(lambda a, b: fn(a, b), gs, ks)
            want = (s - 1).bit_length()   # log2(s) for powers of two
            assert rounds == want, \
                f"engine merge traced {rounds} rounds, want one " \
                f"combine tree of {want}"
        us = time_fn(fn, gs, ks, iters=10, warmup=2)
        tput = N / (us / 1e6)
        # one stats-collecting run records the combine-tree telemetry the
        # timed (stats-off) loop never traces: per-round partial-table
        # widths are the byte cost the merge stage moves over the mesh
        stats = execute(p, gs, ks, mesh=mesh, collect_stats=True)[0].stats
        rows.append({
            "name": f"shard_scaling/grouped_multiop/shards{s}",
            "us_per_call": round(us, 1),
            "tuples_per_s": tput,
            "derived": f"devices={s} tuples_per_s={tput:.3e}",
            "engine_stats": to_jsonable(stats),
        })

        # -- SWAG ------------------------------------------------------------
        qw = Query(ops=SWAG_OPS, window=Window(ws=WS, wa=WA))
        pw = plan(qw, backend="reference", num_shards=s)
        fnw = jax.jit(lambda a, b, p=pw, m=mesh:
                      execute(p, a, b, mesh=m, use_xla_sort=True)[0].values)
        if s > 1:
            rounds = tree_rounds(lambda a, b: fnw(a, b), gw, kw)
            want = (WS // WA - 1).bit_length()   # per-window tree over P
            assert rounds == want, \
                f"swag merge traced {rounds} rounds, want one " \
                f"combine tree of {want}"
        us = time_fn(fnw, gw, kw, iters=10, warmup=2)
        nw = num_windows(N, WS, WA)
        tput = nw * WS / (us / 1e6)
        rows.append({
            "name": f"shard_scaling/swag/shards{s}",
            "us_per_call": round(us, 1),
            "tuples_per_s": tput,
            "derived": f"devices={s} windows={nw} tuples_per_s={tput:.3e}",
        })
    return rows


def _require_cpu() -> None:
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "shard_scaling measures virtual CPU devices in a child process; "
            f"on {jax.default_backend()!r} this process already holds the "
            "device, so the child could not get it — run the sharded "
            "phases of chip_smoke.py --four-chips instead")


def run() -> list[dict]:
    _require_cpu()
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.shard_scaling", "--child"],
        env=env, cwd=root, capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(f"shard_scaling child failed:\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


if __name__ == "__main__":
    if "--child" in sys.argv:
        print(json.dumps(_child()))
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
