"""Event-time subsystem: time-range windows, watermarks, the
bounded-lateness reorder buffer, and the flip-batched two-stack.

The contracts under test:

* reorder buffer — after every push the released set is exactly the
  tuples at or below the watermark, independent of arrival order
  (bit-identity for any shuffle within ``max_lateness``); beyond-bound
  stragglers are *flagged and dropped*, never silently aggregated;
* batch ``Window(range=R, slide=S)`` — windows cover ``[e - R, e)`` at
  slide multiples, on both strategies (per-window replay and the
  two-stack) and both backends (reference and Pallas interpret);
* streaming — every push emits the closed hopping windows its watermark
  has passed, each once, matching a pure-Python oracle of closed windows
  (``_window_oracle``); the sharded path (per-shard buffers, min-merged
  watermark) agrees with the same oracle at the merged watermark.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import eventtime as et
from repro.core.streaming import StreamingAggregator
from repro.query import (Query, Window, execute, init_stream_state, plan,
                         stream_fn)

from _window_oracle import ClosedWindows, got_rows

OPS = ("min", "max", "sum", "count")


def _py_op(op, vals):
    return {"min": min, "max": max, "sum": sum,
            "count": len}[op](vals)


def _window_oracle(g, k, t, wm, rng_, ops):
    """Per-group aggregates over the event-time window [wm - rng_, wm)."""
    buckets: dict[int, list[int]] = {}
    for gi, ki, ti in zip(g, k, t):
        if wm - rng_ <= ti < wm:
            buckets.setdefault(int(gi), []).append(int(ki))
    return {gi: tuple(_py_op(op, vals) for op in ops)
            for gi, vals in sorted(buckets.items())}


def _perturb(rng, ts, lateness):
    """An arrival order shuffled within ``lateness`` time units: tuple x
    never arrives after anything more than ``lateness - 1`` ahead of it,
    so nothing is droppably late."""
    return np.argsort(ts + rng.integers(0, max(lateness, 1), ts.shape[0]),
                      kind="stable")


# ---------------------------------------------------------------------------
# watermarks


def test_watermark_tracker_and_min_merge():
    tr = et.init_tracker()
    tr = et.observe(tr, jnp.array([3, 9, 4], jnp.int32))
    assert int(et.watermark(tr, 2)) == 7
    tr = et.observe(tr, jnp.array([6], jnp.int32))  # no regress
    assert int(et.watermark(tr, 2)) == 7
    wms = jnp.array([17, 3, 9], jnp.int32)
    assert int(et.merge_watermarks(wms)) == 3  # slowest shard gates


# ---------------------------------------------------------------------------
# reorder buffer


@given(seed=st.integers(0, 2**32 - 1), lateness=st.integers(1, 24))
@settings(max_examples=12, deadline=None)
def test_reorder_released_set_is_arrival_order_independent(seed, lateness):
    """Any shuffle within max_lateness releases the same (ts, group, key)
    multiset as in-order ingest — per push and at flush."""
    rng = np.random.default_rng(seed)
    n = 64
    ts = np.sort(rng.integers(0, 200, n)).astype(np.int32)
    g = rng.integers(0, 4, n).astype(np.int32)
    k = rng.integers(-99, 99, n).astype(np.int32)
    pert = _perturb(rng, ts, lateness)
    spec = et.ReorderSpec(capacity=128, max_lateness=lateness)

    def run(order):
        tv, gv, kv = ts[order], g[order], k[order]
        stt = et.init_reorder(spec, jnp.int32)
        released = []
        for i in range(0, n, 32):
            emit, stt = et.reorder_push(
                spec, stt, jnp.array(tv[i:i + 32]), jnp.array(gv[i:i + 32]),
                jnp.array(kv[i:i + 32]))
            live = np.asarray(emit.live)
            released.append(sorted(zip(
                np.asarray(emit.ts)[live].tolist(),
                np.asarray(emit.groups)[live].tolist(),
                np.asarray(emit.keys)[live].tolist())))
        assert int(stt.dropped) == 0
        fl, stt = et.reorder_flush(spec, stt)
        live = np.asarray(fl.live)
        tail = sorted(zip(np.asarray(fl.ts)[live].tolist(),
                          np.asarray(fl.groups)[live].tolist(),
                          np.asarray(fl.keys)[live].tolist()))
        return released, tail

    rel_o, tail_o = run(np.arange(n))
    # a whole-stream shuffle crosses push boundaries, so compare per-push
    # only when the shuffle respects them; the full released stream must
    # always match
    flat_o = sorted(x for batch in rel_o for x in batch) + tail_o
    rel_s, tail_s = run(pert)
    flat_s = sorted(x for batch in rel_s for x in batch) + tail_s
    assert sorted(flat_o) == sorted(flat_s)

    # batch-respecting shuffle: bit-identical per push
    order_w = np.concatenate([i + _perturb(rng, ts[i:i + 32], lateness)
                              for i in range(0, n, 32)])
    rel_w, tail_w = run(order_w)
    assert rel_w == rel_o and tail_w == tail_o


def test_reorder_emissions_are_ts_sorted():
    rng = np.random.default_rng(3)
    ts = np.sort(rng.integers(0, 300, 96)).astype(np.int32)
    pert = _perturb(rng, ts, 16)
    spec = et.ReorderSpec(capacity=128, max_lateness=16)
    stt = et.init_reorder(spec, jnp.int32)
    seen = []
    for i in range(0, 96, 24):
        emit, stt = et.reorder_push(
            spec, stt, jnp.array(ts[pert][i:i + 24]),
            jnp.zeros(24, jnp.int32), jnp.zeros(24, jnp.int32))
        seen.extend(np.asarray(emit.ts)[np.asarray(emit.live)].tolist())
    assert seen == sorted(seen)
    wm = int(stt.max_ts) - 16
    assert all(t <= wm for t in seen)


def test_reorder_flags_and_drops_late_tuples():
    spec = et.ReorderSpec(capacity=16, max_lateness=4)
    stt = et.init_reorder(spec, jnp.int32)
    t = jnp.array([0, 10, 20, 30, 12], jnp.int32)  # 12 < 30 - 4
    emit, stt = et.reorder_push(spec, stt, t, jnp.zeros(5, jnp.int32),
                                jnp.arange(5, dtype=jnp.int32))
    assert int(stt.dropped) == 1
    late = np.asarray(emit.late)
    assert late[4] and late[:4].sum() == 0
    # the dropped key (4, at ts=12) never surfaces downstream
    fl, stt = et.reorder_flush(spec, stt)
    out = set(np.asarray(emit.keys)[np.asarray(emit.live)].tolist())
    out |= set(np.asarray(fl.keys)[np.asarray(fl.live)].tolist())
    assert out == {0, 1, 2, 3}


def test_reorder_n_valid_masks_tail():
    spec = et.ReorderSpec(capacity=16, max_lateness=0)
    stt = et.init_reorder(spec, jnp.int32)
    t = jnp.array([5, 6, 999, 999], jnp.int32)
    emit, stt = et.reorder_push(spec, stt, t, jnp.zeros(4, jnp.int32),
                                jnp.arange(4, dtype=jnp.int32),
                                n_valid=jnp.asarray(2))
    assert int(stt.max_ts) == 6  # dead lanes do not advance the watermark
    fl, _ = et.reorder_flush(spec, stt)
    keys = (np.asarray(emit.keys)[np.asarray(emit.live)].tolist()
            + np.asarray(fl.keys)[np.asarray(fl.live)].tolist())
    assert sorted(keys) == [0, 1]


# ---------------------------------------------------------------------------
# batch time-range windows


def _time_stream(rng, n, t_max=900, n_groups=5):
    g = rng.integers(0, n_groups, n).astype(np.int32)
    k = rng.integers(-100, 100, n).astype(np.int32)
    t = rng.integers(0, t_max, n).astype(np.int32)
    return g, k, t


def _batch_oracle_rows(res, ops):
    """[{group: (vals...)}] per window row, from a batch AggResult."""
    rows = []
    va = np.asarray(res.valid)
    gr = np.asarray(res.groups)
    for i in range(gr.shape[0]):
        row = {}
        for j in range(gr.shape[1]):
            if va[i, j]:
                row[int(gr[i, j])] = tuple(
                    int(np.asarray(res.values[op])[i, j]) for op in ops)
        rows.append(row)
    return rows


def test_batch_grouped_replay_matches_oracle(rng):
    g, k, t = _time_stream(rng, 260)
    R, S = 120, 40
    q = Query(ops=OPS, window=Window(range=R, slide=S))
    res, _ = execute(q, g, k, backend="reference", timestamps=t)
    layout = et.time_window_layout(np.sort(t), R, S)
    assert res.groups.shape[0] == layout.end_times.shape[0]
    rows = _batch_oracle_rows(res, OPS)
    for row, e in zip(rows, layout.end_times.tolist()):
        assert row == _window_oracle(g, k, t, e, R, OPS)


def test_batch_reference_pallas_parity(rng):
    g, k, t = _time_stream(rng, 200)
    q = Query(ops=OPS + ("median",), window=Window(range=90, slide=30))
    r_ref, _ = execute(q, g, k, backend="reference", timestamps=t)
    r_pal, _ = execute(q, g, k, backend="pallas", timestamps=t,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(r_ref.groups),
                                  np.asarray(r_pal.groups))
    for nm in OPS + ("median",):
        np.testing.assert_array_equal(np.asarray(r_ref.values[nm]),
                                      np.asarray(r_pal.values[nm]))


@given(seed=st.integers(0, 2**32 - 1),
       shape=st.sampled_from([(60, 20), (48, 48), (100, 30), (64, 16)]))
@settings(max_examples=10, deadline=None)
def test_twostack_matches_replay_oracle(seed, shape):
    """The flip-batched two-stack equals per-window replay for min/max
    over random variable-width time windows."""
    rng = np.random.default_rng(seed)
    R, S = shape
    n = int(rng.integers(40, 160))
    k = rng.integers(-1000, 1000, n).astype(np.int32)
    t = rng.integers(0, 500, n).astype(np.int32)
    q2 = Query(ops=("min", "max"), group_by=False,
               window=Window(range=R, slide=S))
    assert plan(q2, backend="reference").note is not None
    r2, _ = execute(q2, None, k, backend="reference", timestamps=t)
    qr = Query(ops=("min", "max"), group_by=False,
               window=Window(range=R, slide=S, strategy="replay"))
    rr, _ = execute(qr, None, k, backend="reference", timestamps=t)
    live2 = np.asarray(r2.valid)[:, 0]
    liver = np.asarray(rr.valid)[:, 0]
    np.testing.assert_array_equal(live2, liver)
    for nm in ("min", "max"):
        a = np.asarray(r2.values[nm])[:, 0][live2]
        b = np.asarray(rr.values[nm])[:, 0][live2]
        np.testing.assert_array_equal(a, b)


def test_twostack_pallas_kernel_parity(rng):
    k = rng.integers(-500, 500, 180).astype(np.int32)
    t = rng.integers(0, 600, 180).astype(np.int32)
    q = Query(ops=("min", "max"), group_by=False,
              window=Window(range=100, slide=25))
    r_ref, _ = execute(q, None, k, backend="reference", timestamps=t)
    r_pal, _ = execute(q, None, k, backend="pallas", timestamps=t,
                       interpret=True)
    for nm in ("min", "max"):
        np.testing.assert_array_equal(np.asarray(r_ref.values[nm]),
                                      np.asarray(r_pal.values[nm]))


def test_time_window_layout_needs_concrete_timestamps(rng):
    _, k, t = _time_stream(rng, 64)
    q = Query(ops=("sum",), group_by=False, window=Window(range=32))

    def traced(kk, tt):
        return execute(q, None, kk, backend="reference", timestamps=tt)

    with pytest.raises((ValueError, jax.errors.TracerArrayConversionError)):
        jax.jit(traced)(jnp.array(k), jnp.array(t))


# ---------------------------------------------------------------------------
# streaming event-time


def _stream_setup(num_shards=1, reorder_capacity=64):
    # capacity 64: room for every pane from the oldest window not yet
    # emitted (a push that closes more than ceil(48/16) windows defers the
    # rest, and their panes stay) — nothing is evicted
    q = Query(ops=OPS, streaming=True,
              window=Window(range=48, slide=16, max_lateness=24,
                            reorder_capacity=reorder_capacity, capacity=64))
    p = plan(q, backend="reference",
             **({"num_shards": num_shards} if num_shards > 1 else {}))
    return q, p, stream_fn(p), init_stream_state(p, jnp.int32)


def _sorted_time_stream(rng, n, t_max=400, n_groups=4):
    g = rng.integers(0, n_groups, n).astype(np.int32)
    k = rng.integers(-50, 50, n).astype(np.int32)
    t = np.sort(rng.integers(0, t_max, n)).astype(np.int32)
    return g, k, t


def _dropped(state):
    """Late tuples dropped so far, on either single-device route."""
    return int(state.dropped if hasattr(state, "dropped")
               else state[0].dropped)


def test_streaming_evals_match_watermark_oracle(rng):
    """Every push emits exactly the closed hopping windows its watermark
    has passed (oldest first, at most ceil(R / S) a push), each matching
    the window oracle."""
    N, B, L = 128, 32, 24
    g, k, t = _sorted_time_stream(rng, N)
    q, p, step, state = _stream_setup()
    assert "closed hopping windows" in p.note
    rows = q.window.table_spec().rows
    oracle = ClosedWindows(48, 16, rows, OPS)
    emitted = 0
    for i in range(0, N, B):
        ports, state = step(jnp.array(g[i:i + B]), jnp.array(k[i:i + B]),
                            state, None, jnp.array(t[i:i + B]))
        wm = int(np.max(t[:i + B])) - L
        want = oracle.push(g[i:i + B], k[i:i + B], t[i:i + B], wm)
        assert got_rows(ports, OPS) == want
        emitted += len(want)
    assert emitted > 0


def test_streaming_shuffled_ingest_bit_identical(rng):
    """Per-push emissions are bit-identical between in-order ingest and
    any within-batch, within-lateness shuffle (same prefix, same
    watermark, same closed windows)."""
    N, B, L = 128, 32, 24
    g, k, t = _sorted_time_stream(rng, N)

    def run(gv, kv, tv):
        _, p, step, state = _stream_setup()
        out = []
        for i in range(0, N, B):
            ports, state = step(jnp.array(gv[i:i + B]),
                                jnp.array(kv[i:i + B]), state, None,
                                jnp.array(tv[i:i + B]))
            out.append(got_rows(ports, OPS))
        assert _dropped(state) == 0
        return out

    base = run(g, k, t)
    gw, kw, tw = np.empty_like(g), np.empty_like(k), np.empty_like(t)
    for i in range(0, N, B):
        pp = _perturb(rng, t[i:i + B], L)
        gw[i:i + B] = g[i:i + B][pp]
        kw[i:i + B] = k[i:i + B][pp]
        tw[i:i + B] = t[i:i + B][pp]
    assert run(gw, kw, tw) == base
    assert any(base)


def test_streaming_global_shuffle_matches_at_watermarks(rng):
    """An arbitrary within-lateness shuffle moves tuples across push
    boundaries, so watermarks differ per push — but a window is emitted
    only once closed, so both runs emit the same windows in the same
    order, with bit-identical contents."""
    N, B, L = 128, 32, 24
    g, k, t = _sorted_time_stream(rng, N)
    pert = _perturb(rng, t, L)
    gs_, ks_, ts_ = g[pert], k[pert], t[pert]

    def run(gv, kv, tv):
        _, _, step, state = _stream_setup()
        out = []
        for i in range(0, N, B):
            ports, state = step(jnp.array(gv[i:i + B]),
                                jnp.array(kv[i:i + B]), state, None,
                                jnp.array(tv[i:i + B]))
            out += got_rows(ports, OPS)
        assert _dropped(state) == 0
        return out

    base, shuf = run(g, k, t), run(gs_, ks_, ts_)
    m = min(len(base), len(shuf))
    assert m > 0 and base[:m] == shuf[:m]


def test_sharded_streaming_min_watermark_oracle(rng):
    """num_shards=2: per-shard reorder buffers, releases gated on the
    min-merged watermark; every push emits the closed windows that merged
    watermark has passed, each matching the window oracle."""
    N, B, L = 96, 32, 24
    g, k, t = _sorted_time_stream(rng, N)
    pert = _perturb(rng, t, L)
    g, k, t = g[pert], k[pert], t[pert]
    q, p, step, state = _stream_setup(num_shards=2)
    assert p.num_shards == 2 and "reorder buffer" in p.note
    oracle = ClosedWindows(48, 16, q.window.table_spec().rows, OPS)
    wm_shard = np.full(2, et.TS_MIN, np.int64)
    emitted = 0
    for i in range(0, N, B):
        ports, state = step(jnp.array(g[i:i + B]), jnp.array(k[i:i + B]),
                            state, None, jnp.array(t[i:i + B]))
        halves = t[i:i + B].reshape(2, B // 2)
        wm_shard = np.maximum(wm_shard, halves.max(axis=1))
        gwm = int(wm_shard.min()) - L
        want = oracle.push(g[i:i + B], k[i:i + B], t[i:i + B], gwm)
        assert got_rows(ports, OPS) == want
        emitted += len(want)
    assert emitted > 0


@pytest.mark.parametrize("num_shards", [None, 2])
def test_streaming_aggregator_flush(rng, num_shards):
    """Pushes then ``flush``: every window holding a tuple is emitted
    exactly once, by a push once closed or by the flush."""
    N, B, L = 96, 32, 24
    g, k, t = _sorted_time_stream(rng, N)
    pert = _perturb(rng, t, L)
    g, k, t = g[pert], k[pert], t[pert]
    w = Window(range=48, slide=16, max_lateness=L, reorder_capacity=64,
               capacity=64)
    agg = StreamingAggregator("min", window=w, num_shards=num_shards)
    oracle = ClosedWindows(48, 16, w.table_spec().rows, ("min",))
    wm_shard = np.full(2, et.TS_MIN, np.int64)
    seen = []
    for i in range(0, N, B):
        res = agg.push(g[i:i + B], k[i:i + B], timestamps=t[i:i + B])
        seen += [e for e, _ in got_rows(res, ("min",))]
        if num_shards:
            wm_shard = np.maximum(wm_shard,
                                  t[i:i + B].reshape(2, -1).max(axis=1))
            wm = int(wm_shard.min()) - L
        else:
            wm = int(np.max(t[:i + B])) - L
        assert got_rows(res, ("min",)) == oracle.push(
            g[i:i + B], k[i:i + B], t[i:i + B], wm)
    fin = agg.flush()
    want = oracle.flush()
    assert want and got_rows(fin, ("min",)) == want
    seen += [e for e, _ in want]
    assert seen == sorted(set(seen))


@pytest.mark.parametrize("num_shards", [None, 2])
def test_stream_stats_zero_drops_for_in_contract_shuffles(rng, num_shards):
    """The late-drop counter rides StreamResult.stats: any in-contract
    shuffle (within ``max_lateness``) must report exactly zero dropped
    tuples at every push — and a beyond-contract straggler must show up
    in the counter instead of vanishing silently."""
    N, B, L = 96, 32, 24
    g, k, t = _sorted_time_stream(rng, N)
    pert = _perturb(rng, t, L)
    g, k, t = g[pert], k[pert], t[pert]
    agg = StreamingAggregator(
        "min", window=Window(range=48, slide=16, max_lateness=L,
                             reorder_capacity=64), num_shards=num_shards)
    for i in range(0, N, B):
        res = agg.push(g[i:i + B], k[i:i + B], timestamps=t[i:i + B])
        assert res.stats is not None
        assert int(res.stats["late_dropped"]) == 0
    # one straggler far behind the watermark: flagged and dropped, counted
    stale = np.zeros(B, np.int32)
    res = agg.push(stale, stale, timestamps=stale.astype(np.int64))
    assert int(res.stats["late_dropped"]) >= 1


def test_streaming_push_requires_timestamps():
    _, _, step, state = _stream_setup()
    z = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError, match="timestamps"):
        step(z, z, state, None, None)
    agg = StreamingAggregator("min", window=Window(range=48, slide=16))
    with pytest.raises(ValueError, match="timestamps"):
        agg.push(np.zeros(8, np.int32), np.zeros(8, np.int32))


# ---------------------------------------------------------------------------
# spec validation + backend probes


def test_window_time_clause_validation():
    with pytest.raises(ValueError, match="time-bounded"):
        Window(range=64, ws=32)
    with pytest.raises(ValueError, match="panes is a count-window"):
        Window(range=64, panes=True)
    with pytest.raises(ValueError, match="power of two"):
        Window(range=64, slide=16, wa=6)
    with pytest.raises(ValueError, match="reorder_capacity"):
        Window(range=64, reorder_capacity=48)
    with pytest.raises(ValueError, match="strategy"):
        Window(range=64, strategy="resort")
    with pytest.raises(ValueError, match="event-time parameter"):
        Window(ws=32, slide=8)
    with pytest.raises(ValueError, match="event-time parameter"):
        Window(ws=32, max_lateness=4)
    # tumbling default + per-field defaults
    w = Window(range=64)
    assert w.slide == 64 and w.max_lateness == 0 and w.is_time
    spec = w.store_spec()
    assert spec.is_time and spec.min_capacity == 2


def test_count_window_wa_gt_ws_is_sampling(rng):
    """wa > ws is a deliberate gap: each window covers the first ws
    tuples of its wa-stride and the wa - ws between-window tuples are
    never aggregated."""
    n, ws, wa = 24, 2, 6
    k = rng.integers(0, 50, n).astype(np.int32)
    # poison the gap tuples: if any window read them, max would see 999
    for s in range(0, n, wa):
        k[s + ws:s + wa] = 999
    q = Query(ops=("max", "count"), group_by=False,
              window=Window(ws=ws, wa=wa))
    res, _ = execute(q, None, k, backend="reference")
    va = np.asarray(res.valid)
    mx = np.asarray(res.values["max"])[va]
    assert mx.max() < 999
    want = [int(k[i * wa:i * wa + ws].max())
            for i in range(res.groups.shape[0])]
    assert mx.tolist() == want


def test_grouped_twostack_rejected():
    q = Query(ops=("min",), window=Window(range=64, strategy="twostack"))
    with pytest.raises(ValueError, match="group_by=False"):
        plan(q, backend="reference")


def test_nonpartial_twostack_rejected():
    q = Query(ops=("median",), group_by=False,
              window=Window(range=64, strategy="twostack"))
    with pytest.raises(ValueError, match="replay strategy"):
        plan(q, backend="reference")


def test_pane_backends_reject_time_windows():
    q = Query(ops=("sum",), window=Window(range=64, slide=16))
    with pytest.raises(ValueError, match="re-frame by timestamp"):
        plan(q, backend="pallas-panes")
    with pytest.raises(ValueError, match="per-group windows"):
        plan(q, backend="pallas-panestore")


def test_execute_timestamp_guards(rng):
    g, k, t = _time_stream(rng, 32)
    with pytest.raises(ValueError, match="pass timestamps="):
        execute(Query(ops=("sum",), window=Window(range=64)), g, k,
                backend="reference")
    with pytest.raises(ValueError, match="time-range windows"):
        execute(Query(ops=("sum",), window=Window(ws=8)), g, k,
                backend="reference", timestamps=t)


def test_batch_time_window_cannot_shard(rng):
    q = Query(ops=("sum",), window=Window(range=64, slide=16))
    with pytest.raises(ValueError, match="shard the streaming path"):
        plan(q, backend="reference", num_shards=2)
