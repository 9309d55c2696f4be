"""Observability layer: jit-safe counters, stage tracing, metrics registry.

The load-bearing guarantees:

  * ``collect_stats=True`` never changes a result — bit-identical
    ``AggResult``/``StreamResult`` values against the stats-off run, on
    the reference backend and on the Pallas kernels (property-tested);
  * ``collect_stats=False`` is free — the traced jaxpr carries no counter
    arithmetic (strictly fewer equations than the stats-on trace, stable
    across traces) and the stream carry keeps its pre-observability
    pytree structure;
  * the host-side substrate (spans, registry, exporters) round-trips.
"""
from __future__ import annotations

import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.obs import counters as obs_counters
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.registry import MetricsRegistry, plan_fingerprint
from repro.query import (Query, Window, execute, init_stream_state, plan,
                         stream_fn)
from repro.core.streaming import StreamingAggregator

BACKENDS = ("reference", "pallas")


def _data(seed, n=256, n_groups=8, sort_groups=True):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, n_groups, n).astype(np.int32)
    if sort_groups:
        g = np.sort(g)
    k = rng.integers(-100, 100, n).astype(np.int32)
    return jnp.array(g), jnp.array(k)


def _assert_same_result(a, b):
    assert np.array_equal(np.asarray(a.groups), np.asarray(b.groups))
    assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
    for name in a.values:
        assert np.array_equal(np.asarray(a.values[name]),
                              np.asarray(b.values[name])), name


# ---------------------------------------------------------------------------
# S3: collect_stats on/off bit-identity (property, both backends)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), backend=st.sampled_from(BACKENDS))
def test_grouped_stats_bit_identical(backend, seed):
    g, k = _data(seed)
    q = Query(ops=("sum", "min", "count"))
    off, _ = execute(plan(q, backend=backend), g, k)
    on, _ = execute(plan(q, backend=backend), g, k, collect_stats=True)
    _assert_same_result(off, on)
    assert off.stats is None
    assert int(on.stats["tuples"]) == g.shape[0]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), backend=st.sampled_from(BACKENDS))
def test_windowed_stats_bit_identical(backend, seed):
    g, k = _data(seed, sort_groups=False)
    q = Query(ops=("sum", "min"), window=Window(ws=32, wa=8))
    off, _ = execute(plan(q, backend=backend), g, k)
    on, _ = execute(plan(q, backend=backend), g, k, collect_stats=True)
    _assert_same_result(off, on)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_streaming_stats_bit_identical(seed):
    """Reference backend (streaming carries are reference-only): plain,
    pane-store windowed and event-time streams all push bit-identically
    with the counters carry attached."""
    rng = np.random.default_rng(seed)
    queries = [
        Query(ops=("sum",), streaming=True),
        Query(ops=("sum",), window=Window(ws=16, wa=8, capacity=8),
              streaming=True),
        Query(ops=("min",), window=Window(range=32, slide=8, max_lateness=4,
                                          reorder_capacity=32),
              streaming=True),
    ]
    for q in queries:
        is_time = q.window is not None and q.window.is_time
        plain = q.window is None
        st_off = st_on = None
        t0 = 0
        for _ in range(3):
            g = rng.integers(0, 6, 64).astype(np.int32)
            if plain:
                g = np.sort(g)
            k = rng.integers(-50, 50, 64).astype(np.int32)
            kw = {}
            if is_time:
                kw["timestamps"] = np.arange(t0, t0 + 64)
                t0 += 64
            off, st_off = execute(q, g, k, state=st_off, **kw)
            on, st_on = execute(q, g, k, state=st_on, collect_stats=True,
                                **kw)
            _assert_same_result(off, on)
            assert isinstance(on.stats, dict) and on.stats


# ---------------------------------------------------------------------------
# zero overhead when off


def _num_eqns(jaxpr) -> int:
    total = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for p in eqn.params.values():
            if hasattr(p, "jaxpr"):
                total += _num_eqns(p.jaxpr)
    return total


@pytest.mark.parametrize("q", [
    Query(ops=("sum",), streaming=True),
    Query(ops=("sum",), window=Window(ws=16, wa=8, capacity=8),
          streaming=True),
], ids=["plain", "panestore"])
def test_stats_off_traces_no_counter_ops(q):
    """The stats-off stream step must not pay for the counters: its carry
    keeps the bare engine-state structure (no dict wrapper) and its jaxpr
    is strictly smaller than the stats-on one — and identical across
    traces, so a stats-on trace never pollutes the off path."""
    p = plan(q)
    g = jnp.zeros(64, jnp.int32)
    k = jnp.zeros(64, jnp.int32)

    st_off = init_stream_state(p)
    st_on = init_stream_state(p, collect_stats=True)
    assert isinstance(st_on, tuple) and len(st_on) == 2 \
        and isinstance(st_on[1], dict)
    assert not (isinstance(st_off, tuple) and len(st_off) == 2
                and isinstance(st_off[1], dict))

    step_off = stream_fn(p)
    step_on = stream_fn(p, collect_stats=True)
    jx_off = jax.make_jaxpr(lambda s: step_off(g, k, s))(st_off)
    jx_on = jax.make_jaxpr(lambda s: step_on(g, k, s))(st_on)
    assert _num_eqns(jx_off.jaxpr) < _num_eqns(jx_on.jaxpr)
    jx_off2 = jax.make_jaxpr(lambda s: step_off(g, k, s))(st_off)
    assert str(jx_off) == str(jx_off2)


def test_stats_constancy_enforced_across_stream():
    """A stream started with collect_stats=True must keep it: flipping the
    flag mid-stream would silently change the carry structure, so execute
    rejects the mismatch eagerly."""
    q = Query(ops=("sum",), streaming=True)
    g = jnp.zeros(8, jnp.int32)
    _, state = execute(q, g, g, collect_stats=True)
    with pytest.raises(ValueError, match="collect_stats"):
        execute(q, g, g, state=state)
    _, state = execute(q, g, g)
    with pytest.raises(ValueError, match="collect_stats"):
        execute(q, g, g, state=state, collect_stats=True)


# ---------------------------------------------------------------------------
# sharded telemetry: per-round combine-tree widths


def test_sharded_stats_report_combine_rounds():
    g, k = _data(11)
    q = Query(ops=("sum", "min"))
    res, _ = execute(plan(q, backend="reference", num_shards=4), g, k,
                     collect_stats=True)
    s = res.stats
    assert int(s["num_shards"]) == 4
    widths = np.asarray(s["combine_round_width"])
    assert widths.shape == (2,)          # log2(4) tree rounds
    assert widths[1] == 2 * widths[0]    # pairwise merge doubles the table
    assert np.asarray(s["combine_round_groups"]).shape == (2,)
    assert np.asarray(s["combine_round_bytes"]).shape == (2,)
    off, _ = execute(plan(q, backend="reference", num_shards=4), g, k)
    _assert_same_result(off, res)


def test_streaming_aggregator_surfaces_stats():
    rng = np.random.default_rng(5)
    g = np.sort(rng.integers(0, 6, 64)).astype(np.int32)
    k = rng.integers(0, 50, 64).astype(np.int32)
    agg = StreamingAggregator("sum", collect_stats=True)
    res = agg.push(g, k)
    assert int(res.stats["stream_tuples"]) == 64
    fin = agg.flush()
    assert int(fin.stats["stream_tuples"]) == 64
    # flush resets the counters with the stream
    res2 = agg.push(g, k)
    assert int(res2.stats["stream_tuples"]) == 64


# ---------------------------------------------------------------------------
# counters helpers (None-transparent by contract)


def test_counters_helpers_none_transparent():
    assert obs_counters.bump(None, "x", 1) is None
    assert obs_counters.high_water(None, "x", 1) is None
    assert obs_counters.put(None, "x", 1) is None
    assert obs_counters.ensure(None, ("x",)) is None
    c = obs_counters.init()
    c = obs_counters.ensure(c, ("a", "b"))
    assert set(c) == {"a", "b"}
    c2 = obs_counters.bump(c, "a", jnp.int32(3))
    assert int(c2["a"]) == 3 and int(c["a"]) == 0   # functional update
    c3 = obs_counters.high_water(c2, "b", jnp.int32(7))
    c3 = obs_counters.high_water(c3, "b", jnp.int32(4))
    assert int(c3["b"]) == 7


# ---------------------------------------------------------------------------
# host-side substrate: spans, registry, fingerprint, exporters


def test_trace_capture_nests_dispatch_spans():
    g, k = _data(3)
    with obs_trace.capture() as tr:
        execute(Query(ops=("sum",)), g, k)
    names = [s.name for s in tr.spans]
    assert names.count("plan") == 1 and names.count("dispatch") == 1
    by_name = {s.name: s for s in tr.spans}
    dispatch = by_name["dispatch"]
    assert dispatch.args == {"backend": "reference", "path": "engine"}
    assert dispatch.label() == "dispatch[backend=reference,path=engine]"
    assert by_name["plan"].depth == dispatch.depth
    assert all(s.duration_s >= 0 for s in tr.spans)
    # no capture active -> a bare profiler annotation, nothing recorded
    with obs_trace.span("dispatch", backend="x"):
        pass
    assert len(tr.spans) == 2
    assert isinstance(obs_trace.span("plan"), jax.profiler.TraceAnnotation)


def test_trace_spans_never_sync_the_device(monkeypatch):
    """A span measures host time only: nothing inside ``execute`` may wait
    for the device while a capture is active."""
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or x)
    g, k = _data(4)
    with obs_trace.capture() as tr:
        execute(Query(ops=("sum",), window=Window(ws=32, wa=8)), g, k)
    assert {s.name for s in tr.spans} == {"plan", "dispatch"}
    assert calls == []


# ---------------------------------------------------------------------------
# stage scopes: every stage of a path reaches the compiled program


def _bench_stages():
    """``bench/stages.py``, the benchmark's reader of the scopes."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "bench" / "stages.py"
    spec = importlib.util.spec_from_file_location("bench_stages", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PAPER_OPS = ("min", "max", "sum", "count")
PG_WINDOW = Window(ws=64, ws_per_group=64, wa=16, capacity=20)

#: (query, backend, push length, the path's stages); the pushes end in a
#: partial pane, so framing is a real slice and not a free reshape
STAGED_PATHS = {
    "flat-swag": (Query(ops=PAPER_OPS, window=Window(ws=256, wa=64)),
                  "pallas-panes", 2048 + 32,
                  {"frame", "sort_panes", "pane_merge", "assemble"}),
    "resort": (Query(ops=PAPER_OPS, window=Window(ws=256, wa=64)),
               "pallas", 2048 + 32, {"frame", "window_sort", "assemble"}),
    "per-group": (Query(ops=PAPER_OPS, window=PG_WINDOW),
                  "pallas-panestore", 1024 + 8,
                  {"frame", "dir_scan", "dir_snapshot", "slot_partials",
                   "slot_fold", "assemble"}),
    "per-group-merge": (Query(ops=("min", "median"), window=PG_WINDOW),
                        "pallas-panestore", 1024 + 8,
                        {"frame", "store_push", "replay", "assemble"}),
}


@pytest.mark.parametrize("path", sorted(STAGED_PATHS))
def test_compiled_program_carries_every_stage(path):
    q, backend, n, want = STAGED_PATHS[path]
    p = plan(q, backend=backend)
    g = jnp.zeros(n, jnp.int32)
    text = jax.jit(lambda g, k: execute(p, g, k)[0]).lower(g, g) \
        .compile().as_text()
    got = set(_bench_stages().stage_names(text).values())
    assert got == want
    assert want <= set(obs_trace.STAGES)


def test_reorder_buffer_carries_its_stage():
    from repro.core import eventtime
    spec = eventtime.ReorderSpec(capacity=8, max_lateness=4)
    state = eventtime.init_reorder(spec, jnp.int32)
    x = jnp.arange(32, dtype=jnp.int32)
    text = jax.jit(lambda st, t: eventtime.reorder_push(spec, st, t, t, t)) \
        .lower(state, x).compile().as_text()
    assert set(_bench_stages().stage_names(text).values()) == {"reorder"}


TABLE_QUERY = Query(ops=("count", "max"), streaming=True,
                    window=Window(range=32, slide=16, max_lateness=8,
                                  capacity=64))


def test_table_route_carries_its_stages():
    """The pane-partial table's push lowers to exactly its three stages."""
    p = plan(TABLE_QUERY)
    state = init_stream_state(p)
    x = jnp.arange(32, dtype=jnp.int32)
    step = stream_fn(p)
    text = jax.jit(lambda st, t: step(t, t, st, None, t)) \
        .lower(state, x).compile().as_text()
    got = set(_bench_stages().stage_names(text).values())
    assert got == {"time_partials", "table_merge", "window_emit"}
    assert got <= set(obs_trace.STAGES)
    assert all(obs_trace.STAGES[s][1] for s in got)


def test_table_route_counters():
    """late_dropped, watermark, windows_emitted, pane_rows_hwm and
    pane_evictions ride the carry of a stats-collecting table stream; with
    stats off the step traces no counter op."""
    p = plan(TABLE_QUERY)
    t = jnp.array([0, 5, 17, 40, 20, 41, 60, 70], jnp.int32)  # 20 is late
    g = jnp.array([0, 1, 0, 1, 2, 0, 1, 0], jnp.int32)
    on = init_stream_state(p, collect_stats=True)
    ports, on = stream_fn(p, collect_stats=True)(g, g, on, None, t)
    c = on[1]
    assert int(c["late_dropped"]) == 1
    assert int(c["watermark"]) == 70 - 8
    # windows [-16, 16), [0, 32) and [16, 48) are closed at 62; two rows
    assert int(c["windows_emitted"]) == 2 == int(jnp.sum(ports[3] > 0))
    assert int(c["pane_rows_hwm"]) == 7 and int(c["pane_evictions"]) == 0
    off = init_stream_state(p)
    jx_off = jax.make_jaxpr(lambda s: stream_fn(p)(g, g, s, None, t))(off)
    jx_on = jax.make_jaxpr(
        lambda s: stream_fn(p, collect_stats=True)(g, g, s, None, t))(
            init_stream_state(p, collect_stats=True))
    assert _num_eqns(jx_off.jaxpr) < _num_eqns(jx_on.jaxpr)


def test_stage_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown stage"):
        obs_trace.stage("dir-scan")
    with obs_trace.stage("dir_scan"):
        pass


def test_stage_names_from_hlo():
    """The innermost ``repro.<stage>`` segment of an instruction's
    ``op_name`` names its stage; an instruction without one is left out
    (unstaged)."""
    hlo = """
HloModule jit_timed
%body.1 (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0), metadata={op_name="jit(f)/repro.dir_scan/while/body/closed_call"}
  ROOT %fusion.27 = s32[] fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/repro.dir_scan/while/body/repro.frame/add" source_file="x.py" source_line=3}
}
ENTRY %main.4 (a: s32[8]) -> s32[8] {
  %while.78 = (s32[]) while(%t), condition=%c, body=%body.1, metadata={op_name="jit(f)/jit(g)/repro.dir_scan/while"}
  %repro.sort_panes.1 = s32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/repro.sort_panes/pallas_call"}
  %copy_bitcast_fusion = s32[8] fusion(%a), kind=kLoop, calls=%fc2
  %convert.3 = s32[8] convert(%a), metadata={op_name="jit(f)/jit(_where)/select_n"}
  ROOT %select.2 = s32[8] select(%a, %a, %a), metadata={op_name="jit(f)/repro.assemble/jit(_where)/select_n"}
}
"""
    assert _bench_stages().stage_names(hlo) == {
        "p": "dir_scan", "fusion.27": "frame", "while.78": "dir_scan",
        "repro.sort_panes.1": "sort_panes", "select.2": "assemble"}


def test_metrics_registry_accumulates_and_routes():
    reg = MetricsRegistry()
    reg.observe("reference", "fp", tuples=1000, seconds=1.0)
    reg.observe("reference", "fp", tuples=1000, seconds=1.0)
    reg.observe("pallas", "fp", tuples=4000, seconds=1.0)
    assert reg.tuples_per_s("reference", "fp") == 1000.0
    cell = reg.snapshot()[("reference", "fp")]
    assert cell["calls"] == 2 and cell["tuples"] == 2000.0
    assert reg.best_backend("fp") == "pallas"
    assert reg.best_backend("other") is None
    reg.observe("x", "fp", tuples=1, seconds=0.0)   # ignored, not a div0
    reg.reset()
    assert reg.snapshot() == {}


def test_execute_feeds_process_registry():
    from repro.obs.registry import METRICS
    g, k = _data(9)
    p = plan(Query(ops=("sum",)), backend="reference")
    fp = plan_fingerprint(p)
    before = METRICS.snapshot().get(("reference", fp), {"calls": 0})["calls"] \
        if ("reference", fp) in METRICS.snapshot() else 0
    execute(p, g, k, collect_stats=True)
    cell = METRICS.snapshot()[("reference", fp)]
    assert cell["calls"] == before + 1
    assert cell["tuples_per_s"] > 0


def test_plan_fingerprint_shapes():
    p = plan(Query(ops=("sum", "min")), backend="reference")
    assert plan_fingerprint(p) == "ops=sum,min;group_by=1;path=engine;shards=1"
    pw = plan(Query(ops=("sum",), window=Window(ws=64, wa=16)),
              backend="reference", num_shards=2)
    assert "window=count:ws64:wa16" in plan_fingerprint(pw)
    assert "shards=2" in plan_fingerprint(pw)
    pt = plan(Query(ops=("min",), streaming=True,
                    window=Window(range=32, slide=8, max_lateness=4,
                                  reorder_capacity=16)))
    fp = plan_fingerprint(pt)
    assert "window=time:r32:s8:l4:rc16" in fp and "path=stream" in fp
    # backend is the other half of the registry key, never in the fingerprint
    assert "reference" not in fp


def test_jsonl_export_roundtrip(tmp_path):
    g, k = _data(7)
    res, _ = execute(Query(ops=("sum",)), g, k, num_shards=2,
                     collect_stats=True)
    path = tmp_path / "stats.jsonl"
    obs_export.write_jsonl([{"name": "t", "engine_stats": res.stats}], path)
    [rec] = obs_export.read_jsonl(path)
    assert rec["name"] == "t"
    assert rec["engine_stats"]["tuples"] == g.shape[0]
    assert isinstance(rec["engine_stats"]["combine_round_width"], list)
    json.loads(path.read_text())  # single record: line is plain JSON


def test_prometheus_export_format():
    reg = MetricsRegistry()
    reg.observe("reference", 'fp"x', tuples=100, seconds=1.0)
    txt = obs_export.prometheus_metrics(
        registry=reg, stats={"pane_evictions": jnp.int32(5),
                             "combine_round_width": jnp.array([4, 8])})
    assert '# TYPE repro_observed_tuples_per_s gauge' in txt
    assert 'plan="fp\\"x"' in txt                    # label escaping
    assert 'repro_engine_stat{name="pane_evictions"} 5.0' in txt
    assert 'name="combine_round_width",round="1"} 8.0' in txt


# ---------------------------------------------------------------------------
# S1: eager REPRO_BACKEND validation


def test_env_backend_validated_eagerly(monkeypatch):
    from repro.kernels.registry import resolve_backend
    monkeypatch.setenv("REPRO_BACKEND", "no-such-engine")
    with pytest.raises(ValueError, match=r"REPRO_BACKEND='no-such-engine'"
                                         r".*available backends"):
        resolve_backend()
    with pytest.raises(ValueError):
        plan(Query(ops=("sum",)))
    monkeypatch.setenv("REPRO_BACKEND", "reference")
    assert resolve_backend() == "reference"
    monkeypatch.delenv("REPRO_BACKEND")
    assert resolve_backend() == "auto"
    with pytest.raises(ValueError, match="unknown backend 'bogus'"):
        resolve_backend("bogus")


# ---------------------------------------------------------------------------
# measured-cost backend routing: choose_backend consults the registry


def test_query_fingerprint_matches_plan_fingerprint():
    """choose_backend fingerprints a query *before* a plan exists; the key
    must be byte-identical to the one execute() later records under."""
    from repro.obs.registry import query_fingerprint
    for q, shards in [
        (Query(ops=("sum", "min")), 1),
        (Query(ops=("sum",), window=Window(ws=64, wa=16)), 2),
        (Query(ops=("sum",), window=Window(ws=16, wa=4,
                                           ws_per_group={0: 8})), 1),
        (Query(ops=("sum",), streaming=True), 1),
    ]:
        p = plan(q, backend="reference", num_shards=shards)
        assert query_fingerprint(q, num_shards=shards) == plan_fingerprint(p)


def test_choose_backend_consults_metrics():
    """The S1 wiring: with a seeded registry, auto routing picks the
    measured-fastest *capable* backend; with fewer than two measured
    candidates it falls back to the static capability probe."""
    from repro.kernels.registry import choose_backend
    from repro.obs.registry import METRICS, query_fingerprint
    q = Query(ops=("sum",), window=Window(ws=16, wa=4, ws_per_group={0: 8}))
    fp = query_fingerprint(q)

    METRICS.reset()
    # empty registry -> static probe (CPU: reference)
    assert choose_backend(q)[0] == "reference"
    # a single measured cell proves nothing about the alternatives
    METRICS.observe("reference", fp, tuples=1_000, seconds=1.0)
    assert choose_backend(q)[0] == "reference"
    # two measured candidates -> the numbers decide
    METRICS.observe("pallas-panestore", fp, tuples=50_000, seconds=1.0)
    assert choose_backend(q)[0] == "pallas-panestore"
    assert plan(q).backend == "pallas-panestore"    # auto plan follows
    # a (stale) cell for a backend that cannot run this query never wins
    METRICS.observe("pallas", fp, tuples=10_000_000, seconds=1.0)
    assert choose_backend(q)[0] == "pallas-panestore"
    # the slower measured candidate loses even when observed more recently
    METRICS.observe("reference", fp, tuples=10, seconds=1.0)
    assert choose_backend(q)[0] == "pallas-panestore"
    assert plan(q).note == "auto (measured)"
    METRICS.reset()
    assert choose_backend(q)[0] == "reference"


# ---------------------------------------------------------------------------
# per-group batch-path counters (S2)


@pytest.mark.parametrize("capacity", ["exact", "cut"])
@pytest.mark.parametrize("backend", ["reference", "pallas-panestore"])
def test_pergroup_batch_counters_surface(backend, capacity):
    """What an operator of the per-group store watches, on both backends
    and both of their paths (partial: sum/min, merge-replay: median): no
    eviction when the store holds every group's window, evictions when it
    does not, and the occupancy high-water mark within the store; on the
    kernel backend's partial path, whether the push's directory fell back
    to the per-tuple scan (exactly when the store evicts)."""
    g, k = _data(5, sort_groups=False)          # 8 groups, 256 tuples
    ws, wa = 32, 8
    cap = 8 * (ws // wa + 1) if capacity == "exact" else 12
    w = Window(ws=ws, wa=wa, ws_per_group=ws, capacity=cap)
    seen = []
    for ops in (("sum", "min"), ("sum", "median")):
        res, _ = execute(Query(ops=ops, window=w), g, k, backend=backend,
                         collect_stats=True)
        s = res.stats
        assert not any(name.startswith("pergroup_") for name in s)
        seen.append((int(s["pane_evictions"]), int(s["pane_occupancy_hwm"])))
        if backend == "pallas-panestore" and "median" not in ops:
            assert int(s["pane_dir_fallbacks"]) == (capacity == "cut")
    evictions, hwm = seen[0]
    assert seen[1] == seen[0]                   # one placement policy
    assert 0 < hwm <= cap
    if capacity == "exact":
        assert evictions == 0
    else:
        assert evictions > 0 and hwm == cap


def test_streaming_windowed_dispatch_counters():
    q = Query(ops=("sum",), window=Window(ws=16, wa=8, capacity=8),
              streaming=True)
    res, state = execute(q, jnp.zeros(16, jnp.int32), jnp.ones(16, jnp.int32),
                         collect_stats=True)
    assert int(res.stats["pergroup_partial_ops"]) == 1
    assert int(res.stats["pergroup_merge_ops"]) == 0
    res2, _ = execute(Query(ops=("median",),
                            window=Window(ws=16, wa=8, capacity=8),
                            streaming=True),
                      jnp.zeros(16, jnp.int32), jnp.ones(16, jnp.int32),
                      collect_stats=True)
    assert int(res2.stats["pergroup_partial_ops"]) == 0
    assert int(res2.stats["pergroup_merge_ops"]) == 1


def test_streaming_aggregator_reports_donated_buffers():
    from repro.query import Window as W
    agg = StreamingAggregator("sum", window=W(ws=8, wa=4),
                              collect_stats=True)
    r1 = agg.push(jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.int32))
    assert int(r1.stats["store_donated_buffers"]) == agg._carry_leaves
    r2 = agg.push(jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.int32))
    assert int(r2.stats["store_donated_buffers"]) == 2 * agg._carry_leaves
