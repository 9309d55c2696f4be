"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Every kernel that ``auto`` plans onto a TPU is lowered with
``interpret=False`` and compiled by the TPU compiler (Mosaic) for a
*described* v5e chip, at the sizes ``chip_smoke.py`` runs.  Nothing
executes: these tests catch what interpret mode cannot — block layouts
Mosaic refuses, primitives with no TPU lowering, fast-memory overruns —
without a chip.

The topology is described inside a module-scoped fixture (never at import
time): only one process at a time may load the TPU library, so every
pytest worker must collect the same tests and only the worker that runs
this file loads it.  Keep these tests in this one file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

#: flat SWAG, paper engine window: WS=1024, WA=256 over 2^22 tuples
WS, WA = 1024, 256
NP = (2 ** 22 - WS) // WA + 1 + WS // WA - 1     # panes per push
#: grouped aggregate: 2^24 tuples in 1024-lane tiles (+1 closing tile)
GROUPAGG_TILES = 2 ** 24 // 1024 + 1
#: per-group windows: 2^20 tuples, WA=128, 256 groups of 9 pane slots
PG_WA, PG_CHUNKS, PG_CAP = 128, 2 ** 20 // 128, 256 * 9
PAPER_OPS = ("min", "max", "sum", "count")
#: event-time windows: range 2048 / slide 512 over 2^20 tuples spread on
#: 2^21 time units -> 4096 windows, frames 2048 wide
TIME_WINDOWS, TIME_WCAP = 4096, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


def _i32(*shape):
    return shape, jnp.int32


@pytest.mark.parametrize("ops", [PAPER_OPS, PAPER_OPS + ("distinct_count",),
                                 ("median",)],
                         ids=["paper", "paper-dc", "median"])
def test_pane_kernels_compile(one_chip, ops):
    """``pallas-panes``: pane sort prologue + merge/tails window pass."""
    from repro.kernels.swag import kernel as k

    def fn(g, key):
        pg, pk = k.sort_panes_pallas(g, key, interpret=False)
        return k.swag_pallas_panes(pg, pk, ops, p=WS // WA, interpret=False)

    _compile(one_chip, fn, _i32(NP, WA), _i32(NP, WA))


def test_resort_kernel_compiles(one_chip):
    """``pallas``: per-window re-sort + tails (median and dc included)."""
    from repro.kernels.swag import kernel as k

    nw = (2 ** 22 - WS) // WA + 1
    fn = functools.partial(k.swag_pallas,
                           ops=("median", "distinct_count", "sum"),
                           interpret=False)
    _compile(one_chip, fn, _i32(nw, WS), _i32(nw, WS))


@pytest.mark.parametrize("op", PAPER_OPS)
def test_groupagg_kernel_compiles(one_chip, op):
    """``pallas`` group-by: the tiled groupagg kernel, one op per launch."""
    from repro.core.combiners import get_combiner
    from repro.kernels.groupagg import kernel as k

    fn = functools.partial(k.groupagg_pallas, combiner=get_combiner(op),
                           tile=1024, out_dtype=jnp.int32, interpret=False)
    n = GROUPAGG_TILES * 1024
    _compile(one_chip, fn, _i32(1, n), _i32(1, n))


@pytest.mark.parametrize("op", PAPER_OPS)
def test_groupagg_merge_kernel_compiles(one_chip, op):
    """Sharded ``pallas`` group-by: the merge stage folds the packed shard
    tables with the op's table-level (partial-state) combiner."""
    from repro.core.combiners import get_combiner, partial_combiner
    from repro.kernels.groupagg import kernel as k

    fn = functools.partial(k.groupagg_pallas,
                           combiner=partial_combiner(get_combiner(op)),
                           tile=1024, out_dtype=jnp.int32, interpret=False)
    n = GROUPAGG_TILES * 1024
    _compile(one_chip, fn, _i32(1, n), _i32(1, n))


def test_timeframe_kernel_compiles(one_chip):
    """Grouped event-time windows (replay): the re-sort kernel over
    ``[windows, wcap]`` time frames, median and dc."""
    from repro.kernels.swag import kernel as k

    fn = functools.partial(k.swag_pallas, ops=("median", "distinct_count"),
                           interpret=False)
    _compile(one_chip, fn, _i32(TIME_WINDOWS, TIME_WCAP),
             _i32(TIME_WINDOWS, TIME_WCAP))


def test_pergroup_fused_kernel_compiles(one_chip):
    """``pallas-panestore`` partial-fused: VMEM-resident store + partials."""
    from repro.kernels.swag import kernel as k

    fn = functools.partial(k.pergroup_slot_partials_pallas,
                           ops=PAPER_OPS, interpret=False)
    tup = _i32(PG_CHUNKS, PG_WA)
    slot = _i32(PG_CHUNKS, PG_CAP)
    _compile(one_chip, fn, tup, tup, tup, tup, slot, slot, slot, slot)


def test_pergroup_replay_kernel_compiles(one_chip):
    """``pallas-panestore`` merge-replay: merge + compaction + direct tails."""
    from repro.kernels.swag import kernel as k

    width = 16 * PG_WA   # 9 panes of a 1024-wide window, padded to 16 runs
    fn = functools.partial(k.pergroup_replay_pallas,
                           ops=("median", "distinct_count", "sum"),
                           run=PG_WA, interpret=False)
    _compile(one_chip, fn, _i32(4096, width), _i32(4096, width))


def test_twostack_kernel_compiles(one_chip):
    """Event-time two-stack: the stack-flip scans."""
    from repro.kernels.swag import kernel as k

    def fn(kf, vf, kb, vb):
        return k.twostack_flip_pallas(kf, vf != 0, kb, vb != 0, PAPER_OPS,
                                      interpret=False)

    region = _i32(TIME_WINDOWS, TIME_WCAP)
    _compile(one_chip, fn, region, region, region, region)


@pytest.mark.parametrize("path", ["flat-swag", "per-group"])
def test_kernels_lie_in_their_stages(one_chip, path):
    """The benchmark's two paths through ``execute``: every Pallas kernel
    of the compiled program carries its stage scope, so a profiler trace's
    kernel time splits by stage (``bench/stages.py`` reads the map), and
    each wanted stage of XLA glue (the per-group directory, its
    allocation loop and its fallback scan under one ``lax.cond``) maps
    ops."""
    import importlib.util
    import re
    from pathlib import Path

    from repro.query import Query, Window, execute, plan

    if path == "flat-swag":
        q, backend, n = (Query(ops=PAPER_OPS, window=Window(ws=WS, wa=WA)),
                         "pallas-panes", 2 ** 14)
        want = {"sort_panes", "pane_merge"}
    else:
        q, backend, n = (Query(ops=PAPER_OPS, window=Window(
            ws=1024, ws_per_group=1024, wa=PG_WA, capacity=576)),
            "pallas-panestore", 2 ** 12)
        want = {"slot_partials", "dir_scan"}
    p = plan(q, backend=backend)
    text = _compile(one_chip,
                    lambda g, k: execute(p, g, k, interpret=False)[0],
                    _i32(n), _i32(n))
    spec = importlib.util.spec_from_file_location(
        "bench_stages", Path(__file__).resolve().parents[1] / "bench"
        / "stages.py")
    stages = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stages)
    names = stages.stage_names(text)
    kernels = re.findall(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*'
                         r'custom_call_target="tpu_custom_call"', text,
                         re.M)
    glue = {"dir_scan"}
    assert kernels and {names.get(k) for k in kernels} == want - glue
    assert want <= set(names.values())


def test_pane_partial_table_step_compiles(one_chip):
    """The NEXmark Q5 cell's streaming step at its sizes: 2^16 bids a push
    into a pane-partial table of 2^21 rows, windows of 10 s every 5 s.  An
    XLA program (streaming is a reference-backend feature), with its three
    stages in the compiled text, and its carry small beside 16 GB."""
    from repro.query import (Query, Window, init_stream_state, plan,
                             stream_fn)

    q = Query(ops=("count",), streaming=True,
              window=Window(range=10000, slide=5000, max_lateness=3000,
                            capacity=2 ** 21))
    p = plan(q)
    assert "pane-partial table" in p.note
    step = stream_fn(p)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_stream_state(p)))
    col = jax.ShapeDtypeStruct((2 ** 16,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda g, k, t, st: step(g, k, st, None, t),
                       donate_argnums=(3,)).lower(col, col, col, state) \
        .compile()
    text = compiled.as_text()
    for stage in ("time_partials", "table_merge", "window_emit"):
        assert f"repro.{stage}" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 64 * 2 ** 20
