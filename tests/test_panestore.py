"""Per-group pane store: `Window(ws_per_group=...)` on the shared, evicting
pane buffer.

The contract under test: with enough capacity, each group's replayed window
is **exactly** its last WS_g own tuples (the naive per-group reference, not
pane-quantised); under capacity pressure the globally oldest pane is
evicted and the victim group's window truncates to what the store retains —
pinned here against a pure-Python model of the same retire/evict policy.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import panestore as ps
from repro.core.swag import swag_per_group
from repro.core.streaming import StreamingAggregator
from repro.kernels import registry
from repro.query import Query, Window, execute, plan

WS_MAP = {0: 32, 1: 8}
DEFAULT_WS = 16
ALL_DIRECT = ("sum", "count", "min", "max", "mean", "median",
              "distinct_count")

PY_TAILS = {
    "sum": sum,
    "count": len,
    "min": min,
    "max": max,
    "mean": lambda v: sum(v) / len(v),
    "median": lambda v: sorted(v)[(len(v) - 1) // 2],
    "distinct_count": lambda v: len(set(v)),
}


def _mixed_stream(rng, n, n_groups=5):
    g = rng.integers(0, n_groups, n).astype(np.int32)
    k = rng.integers(0, 60, n).astype(np.int32)
    return g, k


def _naive_windows(g, k, upto, ws_of):
    """keep-last-WS_g-per-group oracle at stream position ``upto``."""
    hist: dict[int, list[int]] = {}
    for gg, kk in zip(g[:upto], k[:upto]):
        hist.setdefault(int(gg), []).append(int(kk))
    return {gid: xs[-ws_of(gid):] for gid, xs in hist.items()}


def _ws_of(gid):
    return WS_MAP.get(gid, DEFAULT_WS)


class StoreModel:
    """Python mirror of the store's retire/evict policy (panes as lists)."""

    def __init__(self, wa, ws_of, cap):
        self.wa, self.ws_of, self.cap = wa, ws_of, cap
        self.panes = []  # {g, base, stamp, tuples}
        self.clock = 0

    def push(self, g, k):
        mine = [p for p in self.panes if p["g"] == g]
        newest = max(mine, key=lambda p: p["base"]) if mine else None
        m = newest["base"] + len(newest["tuples"]) if mine else 0
        if newest is not None and len(newest["tuples"]) < self.wa:
            newest["tuples"].append(k)
        else:
            if len(self.panes) >= self.cap:  # evict globally oldest pane
                self.panes.remove(min(self.panes, key=lambda p: p["stamp"]))
            self.panes.append(dict(g=g, base=m, stamp=self.clock,
                                   tuples=[k]))
            self.clock += 1
        m += 1
        ws = self.ws_of(g)
        self.panes = [p for p in self.panes
                      if not (p["g"] == g and p["base"] + self.wa <= m - ws)]

    def windows(self):
        by_group: dict[int, list] = {}
        for p in sorted(self.panes, key=lambda p: (p["g"], p["base"])):
            by_group.setdefault(p["g"], []).append(p)
        out = {}
        for gid, panes in by_group.items():
            m = panes[-1]["base"] + len(panes[-1]["tuples"])
            lo = m - self.ws_of(gid)
            out[gid] = [x for p in panes for i, x in enumerate(p["tuples"])
                        if p["base"] + i >= lo]
        return out


# ---------------------------------------------------------------------------
# exactness vs the naive keep-last-WS_g reference (ample capacity)
# ---------------------------------------------------------------------------

def test_swag_per_group_matches_naive(rng):
    g, k = _mixed_stream(rng, 192)
    wa = 8
    q = Query(ALL_DIRECT, window=Window(ws=DEFAULT_WS, wa=wa,
                                        ws_per_group=WS_MAP))
    res, state = execute(q, jnp.array(g), jnp.array(k), backend="reference")
    assert state is None
    ne = len(g) // wa
    assert res.groups.shape[0] == ne
    for e in range(ne):
        ref = _naive_windows(g, k, (e + 1) * wa, _ws_of)
        valid = np.array(res.valid[e])
        got_groups = np.array(res.groups[e])[valid].tolist()
        assert got_groups == sorted(ref)
        assert int(res.num_groups[e]) == len(ref)
        for r, gid in enumerate(got_groups):
            for op in ALL_DIRECT:
                want = PY_TAILS[op](ref[gid])
                got = np.array(res.values[op])[e, r]
                np.testing.assert_allclose(got, want, rtol=1e-6), (op, gid)


def test_uniform_int_ws_per_group(rng):
    """ws_per_group as a single int: one per-group window size for every
    group (overriding ws as the default)."""
    g, k = _mixed_stream(rng, 96, n_groups=3)
    q = Query(("sum",), window=Window(ws=64, wa=8, ws_per_group=8))
    res, _ = execute(q, jnp.array(g), jnp.array(k), backend="reference")
    e = res.groups.shape[0] - 1
    ref = _naive_windows(g, k, (e + 1) * 8, lambda gid: 8)
    valid = np.array(res.valid[e])
    for r, gid in enumerate(np.array(res.groups[e])[valid].tolist()):
        assert int(np.array(res.values["sum"])[e, r]) == sum(ref[gid])


# ---------------------------------------------------------------------------
# backend parity: reference replay == pallas kernel replay
# ---------------------------------------------------------------------------

def test_pergroup_backend_parity(rng):
    g, k = _mixed_stream(rng, 160)
    q = Query(ALL_DIRECT, window=Window(ws=DEFAULT_WS, wa=8,
                                        ws_per_group=WS_MAP))
    ref, _ = execute(q, jnp.array(g), jnp.array(k), backend="reference")
    pal, _ = execute(q, jnp.array(g), jnp.array(k),
                     backend="pallas-panestore")
    np.testing.assert_array_equal(np.array(ref.groups), np.array(pal.groups))
    np.testing.assert_array_equal(np.array(ref.valid), np.array(pal.valid))
    np.testing.assert_array_equal(np.array(ref.num_groups),
                                  np.array(pal.num_groups))
    for op in ref.values:
        np.testing.assert_array_equal(np.array(ref.values[op]),
                                      np.array(pal.values[op])), op


# ---------------------------------------------------------------------------
# eviction under capacity pressure — property test vs the Python model
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), cap=st.sampled_from((5, 8, 32)))
def test_property_eviction_matches_model(seed, cap):
    """Random streams through a (possibly too small) store: group sets and
    sum/count per evaluation must match the Python policy model; with
    ample capacity that model degenerates to naive keep-last-WS_g."""
    wa, n = 4, 96
    rng = np.random.default_rng(seed)
    g = rng.integers(0, 6, n).astype(np.int32)
    k = rng.integers(0, 50, n).astype(np.int32)
    ws_map = {0: 16, 1: 4}
    spec = ps.PaneStoreSpec(wa=wa, capacity=cap, default_ws=8,
                            per_group=tuple(ws_map.items()))
    (og, vals, valid, num), _ = swag_per_group(
        jnp.array(g), jnp.array(k), spec=spec, ops=("sum", "count"))

    model = StoreModel(wa, lambda gid: ws_map.get(gid, 8), cap)
    for e in range(n // wa):
        for i in range(e * wa, (e + 1) * wa):
            model.push(int(g[i]), int(k[i]))
        ref = model.windows()
        got = np.array(og[e])[np.array(valid[e])].tolist()
        assert got == sorted(ref), f"eval {e}: {got} != {sorted(ref)}"
        for r, gid in enumerate(got):
            assert int(np.array(vals["sum"])[e, r]) == sum(ref[gid])
            assert int(np.array(vals["count"])[e, r]) == len(ref[gid])
        # ample capacity (6 groups need at most 5+2+4*3 = 19 live slots):
        # the policy model must degenerate to the naive reference
        if cap >= 32:
            naive = _naive_windows(g, k, (e + 1) * wa,
                                   lambda gid: ws_map.get(gid, 8))
            assert {gid: sorted(xs) for gid, xs in ref.items()} == \
                {gid: sorted(xs) for gid, xs in naive.items()}


def test_eviction_truncates_victim_window():
    """Deterministic capacity squeeze: group 0 fills the store, then group
    1's allocations evict 0's oldest panes — 0's effective window shrinks
    below WS_0 while 1 stays exact."""
    wa = 4
    spec = ps.PaneStoreSpec(wa=wa, capacity=5, default_ws=16)
    g = np.array([0] * 16 + [1] * 12, np.int32)
    k = np.arange(28, dtype=np.int32)
    (og, vals, valid, num), _ = swag_per_group(
        jnp.array(g), jnp.array(k), spec=spec, ops=("count", "min"))
    e = 28 // wa - 1
    got = dict(zip(np.array(og[e])[np.array(valid[e])].tolist(),
                   np.array(vals["count"][e])[np.array(valid[e])].tolist()))
    # 1's 12 tuples occupied 3 slots, evicting 2 of 0's 4 panes: 0 keeps 8
    assert got == {0: 8, 1: 12}
    mins = dict(zip(np.array(og[e])[np.array(valid[e])].tolist(),
                    np.array(vals["min"][e])[np.array(valid[e])].tolist()))
    assert mins == {0: 8, 1: 16}  # 0's surviving tuples are 8..15


# ---------------------------------------------------------------------------
# streaming: the carry is the store
# ---------------------------------------------------------------------------

def test_streaming_windowed_matches_naive(rng):
    g, k = _mixed_stream(rng, 128)
    q = Query(("sum", "count"), window=Window(ws=16, wa=8), streaming=True)
    state = None
    for lo in range(0, 128, 16):
        res, state = execute(q, jnp.array(g[lo:lo + 16]),
                             jnp.array(k[lo:lo + 16]), state=state,
                             backend="reference")
        ref = _naive_windows(g, k, lo + 16, lambda gid: 16)
        valid = np.array(res.valid)
        assert np.array(res.groups)[valid].tolist() == sorted(ref)
        for r, gid in enumerate(sorted(ref)):
            assert int(np.array(res.values["sum"])[r]) == sum(ref[gid])
    assert isinstance(state, ps.PaneStoreState)


def test_streaming_aggregator_windowed(rng):
    g, k = _mixed_stream(rng, 96, n_groups=3)
    agg = StreamingAggregator("max", window=Window(ws=8, wa=4,
                                                   ws_per_group={2: 16}))
    for lo in range(0, 96, 32):
        r = agg.push(jnp.array(g[lo:lo + 32]), jnp.array(k[lo:lo + 32]))
    ref = _naive_windows(g, k, 96, lambda gid: 16 if gid == 2 else 8)
    valid = np.array(r.valid)
    assert np.array(r.groups)[valid].tolist() == sorted(ref)
    for i, gid in enumerate(sorted(ref)):
        assert int(np.array(r.values)[i]) == max(ref[gid])
    # flush re-emits the live windows, then resets the store
    f = agg.flush()
    np.testing.assert_array_equal(np.array(f.values), np.array(r.values))
    assert int(agg.push(jnp.array(g[:4]), jnp.array(k[:4])).num_groups) <= 4


def test_make_query_step_pergroup_stream(rng):
    from repro.distributed.steps import make_query_step
    from repro.query import init_stream_state
    g, k = _mixed_stream(rng, 64, n_groups=3)
    q = Query(("sum",), window=Window(ws=8, wa=4), streaming=True)
    step, p = make_query_step(q, backend="reference")
    state = init_stream_state(p)
    res, state = step(jnp.array(g[:32]), jnp.array(k[:32]), state)
    res, state = step(jnp.array(g[32:]), jnp.array(k[32:]), state)
    ref = _naive_windows(g, k, 64, lambda gid: 8)
    valid = np.array(res.valid)
    assert np.array(res.groups)[valid].tolist() == sorted(ref)


# ---------------------------------------------------------------------------
# spec validation + registry capability probes
# ---------------------------------------------------------------------------

def test_window_normalises_ws_per_group():
    w = Window(ws=16, wa=4, ws_per_group={3: 8, 1: 32})
    assert w.ws_per_group == ((1, 32), (3, 8))
    assert w.per_group
    hash(w)  # stays hashable (jit-static / Plan requirement)
    spec = w.store_spec()
    assert spec.per_group == ((1, 32), (3, 8))
    assert spec.max_panes == 32 // 4 + 1


@pytest.mark.parametrize("window,exc", [
    (dict(ws=16, wa=6, ws_per_group={0: 8}), ValueError),    # wa not pow2
    (dict(ws=16, wa=4, ws_per_group={0: 0}), ValueError),    # ws_g <= 0
    (dict(ws=16, wa=4, ws_per_group={0: 8}, capacity=2), ValueError),
    (dict(ws=16, wa=4, ws_per_group="eight"), TypeError),    # bad type
])
def test_pergroup_spec_errors(window, exc):
    with pytest.raises(exc):
        plan(Query(("sum",), window=Window(**window)))


def test_pergroup_plan_conflicts():
    w = Window(ws=16, wa=4, ws_per_group={0: 8})
    with pytest.raises(ValueError, match="presorted"):
        plan(Query(("sum",), window=w, presorted=True))
    with pytest.raises(ValueError, match="panes"):
        plan(Query(("sum",), window=Window(ws=16, wa=4, panes=False,
                                           ws_per_group={0: 8})))


def test_rejection_error_names_reason_and_backends():
    """The registry satellite: an explicit backend that cannot run the
    query raises with the probe's reason AND the available alternatives."""
    q = Query(("sum",), window=Window(ws=16, wa=4, ws_per_group={0: 8}))
    with pytest.raises(ValueError) as ei:
        plan(q, backend="pallas")
    msg = str(ei.value)
    assert "pane store" in msg                      # the probe's reason
    for name in registry.available_backends():      # ...and the list
        assert name in msg, name


def test_panestore_probe_rejections():
    be = registry.get_backend("pallas-panestore")
    assert "pallas-panestore" in registry.available_backends()
    assert be.supports(Query(("sum",))) is not None            # no window
    assert be.supports(Query(("sum",), window=Window(ws=16))) is not None
    w = Window(ws=16, wa=4, ws_per_group={0: 8})
    assert be.supports(Query(("sum",), window=w)) is None
    assert be.supports(Query(("variance",), window=w)) is not None
    assert be.supports(
        Query(("sum",), window=w, streaming=True)) is not None
    # fallback ops still run on the reference backend
    res, _ = execute(Query(("variance",), window=w),
                     jnp.zeros(16, jnp.int32), jnp.ones(16, jnp.int32),
                     backend="reference")
    assert res.groups.shape[0] == 4


@pytest.mark.parametrize("backend", ["reference", "pallas-panestore"])
def test_pergroup_short_stream_empty(backend):
    """A stream shorter than one pane yields zero evaluations (shape
    [0, capacity]) on every backend, like the global-window paths."""
    res, _ = execute(Query(("sum",), window=Window(ws=16, wa=8,
                                                   ws_per_group={0: 8})),
                     jnp.zeros(5, jnp.int32), jnp.ones(5, jnp.int32),
                     backend=backend)
    assert res.groups.shape[0] == 0
    assert res.num_groups.shape == (0,)


def test_spec_capacity_floor():
    with pytest.raises(ValueError, match="capacity"):
        ps.PaneStoreSpec(wa=4, capacity=2, default_ws=16)
    spec = ps.PaneStoreSpec(wa=4, capacity=8, default_ws=16)
    assert spec.min_capacity == 5
    assert spec.runs == 8  # max_panes padded to a power of two


# ---------------------------------------------------------------------------
# batched replay + fused epilogue: bit-exact vs the per-chunk reference
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _fresh_compile_caches(request):
    """The oracle tests below compile many large scan/replay programs; on
    a full-suite run the accumulated LLVM JIT state from ~300 earlier
    tests can segfault XLA:CPU's backend_compile.  Dropping the caches
    first keeps the compiler within its resource budget."""
    if "oracle" in request.node.name or "fused" in request.node.name:
        import jax

        jax.clear_caches()


def _per_chunk_oracle(spec, g, k, ops, state=None):
    """The historical evaluation loop, spelled explicitly: push one WA
    chunk, replay the whole store, repeat.  The oracle the batched paths
    must match bit-for-bit."""
    if state is None:
        state = ps.init_store(spec, jnp.asarray(k).dtype)
    outs = []
    ne = len(g) // spec.wa
    for e in range(ne):
        sl = slice(e * spec.wa, (e + 1) * spec.wa)
        state = ps.push(spec, state, jnp.asarray(g[sl]), jnp.asarray(k[sl]))
        outs.append(ps.replay(spec, state, list(ops)))
    stack = lambda *xs: np.stack([np.asarray(x) for x in xs])
    og = stack(*(o[0] for o in outs))
    vals = {nm: stack(*(o[1][nm] for o in outs)) for nm in outs[0][1]}
    valid = stack(*(o[2] for o in outs))
    num = stack(*(o[3] for o in outs))
    return (og, vals, valid, num), state


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), cap=st.sampled_from((5, 8, 40)),
      float_keys=st.booleans())
def test_property_batched_matches_per_chunk_oracle(seed, cap, float_keys):
    """The tentpole invariant: one batched ``swag_per_group`` call (partial
    fast path or single batched merge pass, by op mix) reproduces the
    per-chunk push+replay loop exactly — including eviction-boundary and
    capacity-squeeze streams (cap=5 keeps the store permanently starved)
    — and its reconstructed final state continues the stream exactly."""
    wa, n = 4, 96
    rng = np.random.default_rng(seed)
    g = rng.integers(0, 6, n).astype(np.int32)
    if float_keys:
        k = rng.normal(scale=30.0, size=n).astype(np.float32)
    else:
        k = rng.integers(-50, 50, n).astype(np.int32)
    spec = ps.PaneStoreSpec(wa=wa, capacity=cap, default_ws=8,
                            per_group=((0, 16), (1, 4)))
    for ops in (("sum", "count", "min", "max", "mean"),   # pure partial
                ALL_DIRECT):                              # merge present
        (og, vals, valid, num), ostate = _per_chunk_oracle(spec, g, k, ops)
        (bg, bvals, bvalid, bnum), bstate = swag_per_group(
            jnp.array(g), jnp.array(k), spec=spec, ops=list(ops))
        np.testing.assert_array_equal(og, np.asarray(bg))
        np.testing.assert_array_equal(valid, np.asarray(bvalid))
        np.testing.assert_array_equal(num, np.asarray(bnum))
        for nm in vals:
            np.testing.assert_array_equal(vals[nm], np.asarray(bvals[nm]),
                                          err_msg=nm)
        # continuation off the (reconstructed) final state
        g2 = rng.integers(0, 6, 3 * wa).astype(np.int32)
        k2 = (rng.normal(scale=30.0, size=3 * wa).astype(np.float32)
              if float_keys else
              rng.integers(-50, 50, 3 * wa).astype(np.int32))
        (og2, vals2, _, _), _ = _per_chunk_oracle(spec, g2, k2, ops,
                                                  state=ostate)
        (bg2, bvals2, _, _), _ = swag_per_group(
            jnp.array(g2), jnp.array(k2), spec=spec, ops=list(ops),
            state=bstate)
        np.testing.assert_array_equal(og2, np.asarray(bg2))
        for nm in vals2:
            np.testing.assert_array_equal(vals2[nm], np.asarray(bvals2[nm]),
                                          err_msg=f"{nm} (continuation)")


def test_fused_kernel_partial_path_parity(rng):
    """All-partial op sets ride the fused push+replay kernel on the
    pallas-panestore backend — outputs (and dtypes) must equal the
    reference batch path exactly, under capacity pressure too."""
    g, k = _mixed_stream(rng, 160)
    for cap in (None, 6):
        w = Window(ws=DEFAULT_WS, wa=8, ws_per_group=WS_MAP, capacity=cap)
        q = Query(("sum", "count", "min", "max", "mean"), window=w)
        assert registry.pergroup_kernel_path(q) == "partial-fused"
        ref, _ = execute(q, jnp.array(g), jnp.array(k), backend="reference")
        pal, _ = execute(q, jnp.array(g), jnp.array(k),
                         backend="pallas-panestore")
        np.testing.assert_array_equal(np.array(ref.groups),
                                      np.array(pal.groups))
        np.testing.assert_array_equal(np.array(ref.valid),
                                      np.array(pal.valid))
        for op in ref.values:
            assert ref.values[op].dtype == pal.values[op].dtype, op
            np.testing.assert_array_equal(np.array(ref.values[op]),
                                          np.array(pal.values[op])), op


def test_pergroup_kernel_path_probe():
    w = Window(ws=16, wa=4, ws_per_group={0: 8})
    assert registry.pergroup_kernel_path(
        Query(("sum", "mean"), window=w)) == "partial-fused"
    assert registry.pergroup_kernel_path(
        Query(("sum", "median"), window=w)) == "merge-replay"
    # float keys push reorder-sensitive sum/mean off the partial path
    assert registry.pergroup_kernel_path(
        Query(("sum",), window=w), key_dtype=jnp.float32) == "merge-replay"
    assert registry.pergroup_kernel_path(
        Query(("min", "max"), window=w),
        key_dtype=jnp.float32) == "partial-fused"


def test_streaming_push_traces_once(rng):
    """Recompile guard: the donated-carry streaming step must trace exactly
    once across pushes — a second trace means the donation or carry
    structure changed shape between calls."""
    g, k = _mixed_stream(rng, 96, n_groups=3)
    agg = StreamingAggregator("sum", window=Window(ws=8, wa=4))
    for lo in range(0, 96, 32):
        agg.push(jnp.array(g[lo:lo + 32]), jnp.array(k[lo:lo + 32]))
    assert agg._step._cache_size() == 1


# ---------------------------------------------------------------------------
# the write plan by pane allocations: bit-exact vs the per-tuple scan
# ---------------------------------------------------------------------------

PLAN_FIELDS = ("slots", "lanes", "seqs", "own_s", "cnt_s", "lo_s",
               "sortmask", "ugroups", "num")


def _plan_stream(regime, rng):
    """``(spec, groups)`` of one directory regime, drawn from ``rng``."""
    wa = int(rng.choice((1, 2, 4, 8)))
    ne = int(rng.integers(1, 13))
    per_group = ()
    if regime == "squeezed":
        # ws = 1 (mod WA): a pane retires on the tuple that opens another
        ws = wa * int(rng.integers(0, 3)) + 1
        ne = max(ne, -(-ws // wa) + 2)
    else:
        ws = wa * int(rng.integers(1, 5)) if regime == "exact" else \
            int(rng.integers(1, 40))
    n_groups = {"one-group": 1, "ne-1": int(rng.integers(1, 9))}.get(
        regime, int(rng.integers(2, 7)))
    if regime == "ne-1":
        ne = 1
    if regime == "overrides":
        # off the pane grid, one past it, and narrower than a pane
        per_group = ((0, 3 * wa + 1 + int(rng.integers(1, wa + 1))
                      if wa > 1 else 5),
                     (1, 2 * wa + 1), (2, max(1, wa // 2)))
    spec0 = ps.PaneStoreSpec(wa=wa, capacity=64, default_ws=ws,
                             per_group=per_group)
    need = sum(-(-spec0.ws_of(jnp.int32(gid)).item() // wa) + 1
               for gid in range(n_groups))
    cap = {"ample": need + int(rng.integers(1, 9)), "exact": need,
           "squeezed": spec0.min_capacity,
           "more-groups": spec0.min_capacity}.get(regime, need)
    if regime == "more-groups":
        n_groups = cap + int(rng.integers(1, 4))
        ne = max(ne, -(-n_groups // wa))
    g = rng.integers(0, n_groups, ne * wa + int(rng.integers(0, wa))
                     ).astype(np.int32)
    if regime == "more-groups":
        g[:n_groups] = np.arange(n_groups)
    if regime == "squeezed":
        # group 1 holds a slot while group 0 fills the rest: each of group
        # 0's later allocations overfills the store on the tuple that
        # retires one of its panes (the fallback test counts allocations
        # before retirements)
        g[0] = 1
        g[1:] = 0
    spec = ps.PaneStoreSpec(wa=wa, capacity=max(cap, spec0.min_capacity),
                            default_ws=ws, per_group=per_group)
    return spec, g


def _check_plan_matches_scan(spec, g):
    """``pergroup_write_plan`` against the per-tuple scan, element by
    element and dtype by dtype; returns the fallback count."""
    import jax

    from repro.core.swag import (_plan_scan, _plan_snapshot, frame_panes,
                                 pergroup_write_plan)

    def scan_plan(g):
        p = _plan_scan(spec, frame_panes(g, spec.wa, g.shape[-1] // spec.wa),
                       True)
        return _plan_snapshot(spec, *p[:6]), p[6], p[7]

    plan, counters = jax.jit(
        lambda g: pergroup_write_plan(spec, g, counters={}))(g)
    want, evictions, hwm = jax.jit(scan_plan)(g)
    for name, got, ref in zip(PLAN_FIELDS, plan, want):
        assert got.dtype == ref.dtype, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      err_msg=name)
    assert int(counters["pane_evictions"]) == int(evictions)
    assert int(counters["pane_occupancy_hwm"]) == int(hwm)
    fallbacks = int(counters["pane_dir_fallbacks"])
    # the scan is taken exactly when some allocation finds the store full
    assert fallbacks == int(int(evictions) > 0)
    return fallbacks


@pytest.mark.parametrize("regime", ["ample", "exact", "squeezed",
                                    "overrides", "one-group", "more-groups",
                                    "ne-1"])
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_write_plan_matches_tuple_scan(regime, seed):
    """The directory by pane allocations gives the per-tuple scan's plan,
    all nine outputs and the three counters, in every regime; it falls
    back to the scan exactly when the push's panes do not fit."""
    spec, g = _plan_stream(regime, np.random.default_rng(seed))
    fallbacks = _check_plan_matches_scan(spec, jnp.asarray(g))
    assert fallbacks == (regime in ("squeezed", "more-groups"))


def test_write_plan_benchmark_shape_takes_no_fallback():
    """The benchmark cell's store, scaled down to 2^14 tuples: 64 uniform
    groups, WA 128, WS 1024, 576 = 64 x (1024/128 + 1) slots."""
    rng = np.random.default_rng(14)
    spec = ps.PaneStoreSpec(wa=128, capacity=576, default_ws=1024)
    g = rng.integers(0, 64, 2 ** 14).astype(np.int32)
    assert _check_plan_matches_scan(spec, jnp.asarray(g)) == 0
