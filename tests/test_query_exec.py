"""Two-phase mergeable-state execution (``repro.distributed.query_exec``).

Three layers of guarantees:

  * the **algebra**: ``merge(partials(A), partials(B)) == partials(A ++ B)``
    for every registered mergeable combiner — including the dc
    boundary-equality case (split mid-group, equal boundary keys) and the
    empty-shard identity — as a hypothesis property;
  * **logical shards**: ``execute(..., num_shards=S)`` runs the identical
    partition -> local -> merge -> finalize pipeline on one device and must
    be bit-identical to single-device execution for grouped, windowed and
    streaming queries (always runs, no mesh needed);
  * **the mesh**: the same pipeline under ``shard_map`` over an 8-way
    host-platform mesh (the CI ``multidevice`` job sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the tests skip
    when fewer devices exist).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core import StreamingAggregator
from repro.core.combiners import ALL_OPS, get_combiner
from repro.distributed import query_exec as QX
from repro.kernels import registry
from repro.query import Query, Window, execute, plan

from conftest import PY_OPS, py_group_aggregate, sorted_stream

MERGEABLE = tuple(op for op in ALL_OPS if get_combiner(op).mergeable)


def _mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return jax.make_mesh((8,), ("shards",), devices=jax.devices()[:8])


def _sorted_full(rng, n, n_groups):
    g, k = sorted_stream(rng, n, n_groups, full_sort=True)
    return jnp.array(g), jnp.array(k)


def _assert_result_equal(ref, got, *, names=None):
    v = np.array(ref.valid)
    assert np.array_equal(v, np.array(got.valid))
    assert np.array_equal(np.array(ref.num_groups), np.array(got.num_groups))
    assert np.array_equal(np.array(ref.groups)[v], np.array(got.groups)[v])
    for name in names or ref.values:
        assert np.array_equal(np.array(ref.values[name])[v],
                              np.array(got.values[name])[v]), name


# ---------------------------------------------------------------------------
# the partial-state merge algebra
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       cut=st.sampled_from((0, 1, 37, 64, 128)),  # bounded shape set: the
       # split point changes the trace, so keep the compile cache warm
       key_max=st.sampled_from((3, 1000)))
def test_merge_partials_matches_full(seed, cut, key_max):
    """merge_partial(partials(A), partials(B)) == partials(A ++ B) for every
    mergeable combiner at once: the stream is split at an arbitrary point
    (mid-group splits exercise dc's boundary rule; ``key_max=3`` forces
    boundary *key equality*, the double-count case; ``cut=0`` is the
    empty-shard identity)."""
    rng = np.random.default_rng(seed)
    g, k = sorted_stream(rng, 128, 7, key_max=key_max, full_sort=True)
    gj, kj = jnp.array(g), jnp.array(k)

    full = E.multi_engine_partials(gj, kj, MERGEABLE)
    pa = E.multi_engine_partials(gj[:cut], kj[:cut], MERGEABLE)
    pb = E.multi_engine_partials(gj[cut:], kj[cut:], MERGEABLE)
    merged = E.combine_partial_tables(pa, pb, MERGEABLE, key_dtype=jnp.int32)

    n = int(full.num_groups)
    assert int(merged.num_groups) == n
    assert np.array_equal(np.array(merged.groups[:n]),
                          np.array(full.groups[:n]))
    _, fv, _, _ = E.finalize_partial_table(full, MERGEABLE)
    _, mv, _, _ = E.finalize_partial_table(merged, MERGEABLE)
    for name in MERGEABLE:
        a, b = np.array(fv[name][:n]), np.array(mv[name][:n])
        if name == "variance":  # float re-association: ~ulp, not bit-exact
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        else:
            assert np.array_equal(a, b), name


def test_dc_boundary_subtract_exact():
    """The distributed rule, verbatim: equal boundary keys across the shard
    cut are counted once."""
    g = jnp.array([0, 0, 0, 0], jnp.int32)
    k = jnp.array([1, 5, 5, 9], jnp.int32)
    full = E.multi_engine_partials(g, k, ("distinct_count",))
    pa = E.multi_engine_partials(g[:2], k[:2], ("distinct_count",))
    pb = E.multi_engine_partials(g[2:], k[2:], ("distinct_count",))
    m = E.combine_partial_tables(pa, pb, ("distinct_count",),
                                 key_dtype=jnp.int32)
    _, fv, _, _ = E.finalize_partial_table(full, ("distinct_count",))
    _, mv, _, _ = E.finalize_partial_table(m, ("distinct_count",))
    assert int(fv["distinct_count"][0]) == 3
    assert int(mv["distinct_count"][0]) == 3


def test_empty_shard_is_identity(rng):
    g, k = _sorted_full(rng, 64, 5)
    pb = E.multi_engine_partials(g, k, MERGEABLE)
    empty = E.empty_partial_table(32, MERGEABLE, jnp.int32)
    for a, b in ((empty, pb),):
        m = E.combine_partial_tables(a, b, MERGEABLE, key_dtype=jnp.int32)
        n = int(pb.num_groups)
        assert int(m.num_groups) == n
        _, mv, _, _ = E.finalize_partial_table(m, MERGEABLE)
        _, bv, _, _ = E.finalize_partial_table(pb, MERGEABLE)
        for name in MERGEABLE:
            assert np.array_equal(np.array(mv[name][:n]),
                                  np.array(bv[name][:n])), name


def test_combine_tree_nonpow2_shards(rng):
    """A 3-shard tree pads with the identity table and still matches."""
    g, k = _sorted_full(rng, 96, 6)
    full = E.multi_engine_partials(g, k, ("sum", "distinct_count"))
    parts = [E.multi_engine_partials(g[i * 32:(i + 1) * 32],
                                     k[i * 32:(i + 1) * 32],
                                     ("sum", "distinct_count"))
             for i in range(3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    merged = QX.combine_tree(stacked, ("sum", "distinct_count"),
                             key_dtype=jnp.int32)
    n = int(full.num_groups)
    assert int(merged.num_groups) == n
    _, fv, _, _ = E.finalize_partial_table(full, ("sum", "distinct_count"))
    _, mv, _, _ = E.finalize_partial_table(merged, ("sum", "distinct_count"))
    for name in fv:
        assert np.array_equal(np.array(fv[name][:n]),
                              np.array(mv[name][:n])), name


def test_argminmax_not_mergeable():
    for op in ("argmin", "argmax"):
        with pytest.raises(ValueError, match="partial-state merge"):
            plan(Query(ops=(op,)), backend="reference", num_shards=2)


def test_sharded_plan_validation():
    with pytest.raises(ValueError, match="pane store"):
        plan(Query(("sum",), window=Window(ws=16, wa=4, ws_per_group={0: 8})),
             backend="reference", num_shards=2)
    with pytest.raises(ValueError, match="shared pane store"):
        plan(Query(("sum",), window=Window(ws=16, wa=4), streaming=True),
             backend="reference", num_shards=2)
    with pytest.raises(ValueError, match="presorted"):
        plan(Query(("sum",), window=Window(ws=16), presorted=True),
             backend="reference", num_shards=2)
    with pytest.raises(ValueError, match="partial states"):
        plan(Query(ops=("mean",)), backend="pallas", num_shards=2)
    # the stage pipeline is explicit on the plan
    p = plan(Query(ops=("sum",)), backend="reference", num_shards=4)
    assert p.stages == ("partition", "local", "merge", "finalize")
    assert plan(Query(ops=("sum",))).stages == ("local", "finalize")


def test_partition_needs_divisibility(rng):
    g, k = _sorted_full(rng, 100, 5)
    with pytest.raises(ValueError, match="divide"):
        execute(Query(ops=("sum",)), g, k, backend="reference", num_shards=8)


def test_auto_probe_falls_back_to_reference_for_sharded(monkeypatch):
    """An *auto*-chosen kernel backend must not turn a shardable query into
    a plan failure on accelerator meshes: dc's kernel output is not its
    partial state, so auto falls back to reference (an explicit request
    still raises)."""
    monkeypatch.delenv(registry.BACKEND_ENV, raising=False)

    class _Dev:
        platform = "tpu"

    p = plan(Query(ops=("dc",)), num_shards=2, devices=[_Dev()])
    assert p.backend == "reference"
    assert "cannot shard" in p.note
    with pytest.raises(ValueError, match="cannot shard"):
        plan(Query(ops=("dc",)), backend="pallas", num_shards=2)
    # median rides the run channel — pallas + sharded median stays valid
    assert plan(Query(ops=("sum", "median")), backend="pallas",
                num_shards=2).backend == "pallas"


def test_nonpow2_shards_uniform_result_widths(rng):
    """pow2 shard padding must not leak into the result: every column
    (incl. the run-channel median) keeps the single-device width."""
    g, k = _sorted_full(rng, 300, 7)
    q = Query(ops=("sum", "median"))
    ref, _ = execute(q, g, k, backend="reference")
    sh, _ = execute(q, g, k, backend="reference", num_shards=3)
    assert sh.groups.shape == ref.groups.shape
    assert sh.valid.shape == ref.valid.shape
    for name in sh.values:
        assert sh.values[name].shape == ref.values[name].shape, name
    _assert_result_equal(ref, sh)

    # streaming: N+1 output slots regardless of the pow2 padding
    qs = Query(ops=("sum",), streaming=True)
    ra, _ = execute(qs, g[:300], k[:300], backend="reference")
    rb, _ = execute(qs, g[:300], k[:300], backend="reference", num_shards=3)
    assert rb.groups.shape == ra.groups.shape == (301,)
    _assert_result_equal(ra, rb)


def test_window_run_channel_only_sharded(rng):
    """All-run-channel windowed query (median alone): the local phase is
    just the pane sort, and results stay bit-identical."""
    g = jnp.array(rng.integers(0, 8, 1024).astype(np.int32))
    k = jnp.array(rng.integers(0, 500, 1024).astype(np.int32))
    q = Query(ops=("median",), window=Window(ws=256, wa=64))
    ref, _ = execute(q, g, k, backend="reference", use_xla_sort=True)
    sh, _ = execute(q, g, k, backend="reference", num_shards=4,
                    use_xla_sort=True)
    _assert_result_equal(ref, sh)


# ---------------------------------------------------------------------------
# logical shards (no mesh): the same pipeline, one device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [2, 8])
def test_engine_sharded_matches_oracle(rng, num_shards):
    g, k = sorted_stream(rng, 512, 11, full_sort=True)
    q = Query(ops=("sum", "count", "mean", "dc", "median"))
    res, _ = execute(q, jnp.array(g), jnp.array(k), backend="reference",
                     num_shards=num_shards)
    n = int(res.num_groups)
    for op in ("sum", "count", "mean", "distinct_count", "median"):
        og, ov = py_group_aggregate(g, k, PY_OPS[op])
        assert n == len(og)
        np.testing.assert_array_equal(np.array(res.groups[:n]), og)
        np.testing.assert_allclose(np.array(res.values[op][:n]), ov,
                                   rtol=1e-6)


def test_engine_sharded_bit_identical(rng):
    g, k = _sorted_full(rng, 1024, 16)
    q = Query(ops=("sum", "min", "max", "count", "mean", "dc", "median",
                   "first", "last"))
    ref, _ = execute(q, g, k, backend="reference")
    sh, _ = execute(q, g, k, backend="reference", num_shards=8)
    _assert_result_equal(ref, sh)


def test_engine_sharded_n_valid(rng):
    g, k = _sorted_full(rng, 256, 9)
    q = Query(ops=("sum", "dc"))
    ref, _ = execute(q, g[:200], k[:200], backend="reference", num_shards=4)
    pad, _ = execute(q, g, k, n_valid=jnp.asarray(200), backend="reference",
                     num_shards=8)
    n = int(ref.num_groups)
    assert n == int(pad.num_groups)
    for name in ref.values:
        np.testing.assert_array_equal(np.array(ref.values[name][:n]),
                                      np.array(pad.values[name][:n]))


@pytest.mark.parametrize("ws,wa", [(1024, 256), (96, 24)])
def test_window_sharded_bit_identical(rng, ws, wa):
    """Pane-compatible windows run the pane two-phase pipeline; other
    shapes fall back to window-axis partitioning — both bit-identical."""
    g = jnp.array(rng.integers(0, 16, 2048).astype(np.int32))
    k = jnp.array(rng.integers(0, 1000, 2048).astype(np.int32))
    q = Query(ops=("sum", "min", "dc", "median", "mean"),
              window=Window(ws=ws, wa=wa))
    ref, _ = execute(q, g, k, backend="reference", use_xla_sort=True)
    sh, _ = execute(q, g, k, backend="reference", num_shards=8,
                    use_xla_sort=True)
    _assert_result_equal(ref, sh)


def test_streaming_sharded_bit_identical(rng):
    g, k = _sorted_full(rng, 512, 13)
    q = Query(ops=("sum", "count", "dc"), streaming=True)
    sa = sb = None
    for lo in range(0, 512, 128):
        ra, sa = execute(q, g[lo:lo + 128], k[lo:lo + 128], state=sa,
                         backend="reference")
        rb, sb = execute(q, g[lo:lo + 128], k[lo:lo + 128], state=sb,
                         backend="reference", num_shards=4)
        _assert_result_equal(ra, rb)
    # the rolling carries agree too (same group/state/emitted)
    for ca, cb in zip(sa, sb):
        assert int(ca.group) == int(cb.group)
        assert int(ca.emitted) == int(cb.emitted)
        for la, lb in zip(jax.tree.leaves(ca.state),
                          jax.tree.leaves(cb.state)):
            np.testing.assert_array_equal(np.array(la), np.array(lb))


def test_streaming_aggregator_per_shard_pushes(rng):
    g, k = sorted_stream(rng, 512, 9)
    ref = StreamingAggregator("sum")
    sh = StreamingAggregator("sum", num_shards=4)
    for lo in range(0, 512, 128):
        want = ref.push(jnp.array(g[lo:lo + 128]), jnp.array(k[lo:lo + 128]))
        got = sh.push(jnp.array(g[lo:lo + 128]).reshape(4, 32),
                      jnp.array(k[lo:lo + 128]).reshape(4, 32))
        np.testing.assert_array_equal(np.array(want.values),
                                      np.array(got.values))
        np.testing.assert_array_equal(np.array(want.valid),
                                      np.array(got.valid))
        np.testing.assert_array_equal(np.array(want.rr_port),
                                      np.array(got.rr_port))
    np.testing.assert_array_equal(np.array(ref.flush().values),
                                  np.array(sh.flush().values))


def test_pallas_engine_sharded_parity(rng):
    """Kernel backends keep their per-shard kernels: the tiled groupagg
    kernel runs per shard (its output *is* the partial state for
    PARTIAL_OPS) and one more kernel pass over the packed tables merges
    them."""
    g, k = sorted_stream(rng, 512, 9)
    q = Query(ops=("sum", "max"))
    ref, _ = execute(q, jnp.array(g), jnp.array(k), backend="reference")
    sh, _ = execute(q, jnp.array(g), jnp.array(k), backend="pallas",
                    num_shards=4, tile=128)
    _assert_result_equal(ref, sh)


@pytest.mark.parametrize("num_shards,n_valid", [(4, None), (3, None),
                                                (4, 301), (2, 0)])
def test_pallas_engine_sharded_kernel_merge(rng, num_shards, n_valid):
    """The kernel merge stage: groups spanning shard boundaries fold
    exactly for every KERNEL_STATE_OP (counts summed, not counted), with
    a padded tail, a shard count that is not a power of two, and an empty
    stream prefix; its telemetry reports the one merge pass."""
    g, k = sorted_stream(rng, 384, 5)
    q = Query(ops=("sum", "count", "min", "max"))
    nv = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
    ref, _ = execute(q, jnp.array(g), jnp.array(k), n_valid=nv,
                     backend="reference")
    sh, _ = execute(q, jnp.array(g), jnp.array(k), n_valid=nv,
                    backend="pallas", num_shards=num_shards, tile=128,
                    collect_stats=True)
    _assert_result_equal(ref, sh._replace(stats=None))
    assert int(sh.stats["combine_rounds"]) == 1
    assert np.asarray(sh.stats["combine_round_width"]).tolist() == [384]
    assert np.asarray(sh.stats["combine_round_groups"]).tolist() == [
        int(ref.num_groups)]


# ---------------------------------------------------------------------------
# device-aware registry probes
# ---------------------------------------------------------------------------

def test_choose_backend_device_aware(no_env_backend):
    q = Query(ops=("sum",), window=Window(ws=64, wa=16))

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    assert registry.choose_backend(q, [_Dev("cpu")])[0] == "reference"
    # an accelerator mesh flips the very same query to the pane kernels
    assert registry.choose_backend(q, [_Dev("tpu")])[0] == "pallas-panes"


@pytest.fixture
def no_env_backend(monkeypatch):
    monkeypatch.delenv(registry.BACKEND_ENV, raising=False)


# ---------------------------------------------------------------------------
# the 8-way host-platform mesh (CI: multidevice job)
# ---------------------------------------------------------------------------

def test_mesh_engine_parity(rng, no_env_backend):
    mesh = _mesh8()
    g, k = _sorted_full(rng, 2048, 16)
    q = Query(ops=("sum", "min", "max", "count", "mean", "dc", "median"))
    ref, _ = execute(q, g, k, backend="reference")
    sh, _ = execute(q, g, k, mesh=mesh)
    _assert_result_equal(ref, sh)
    # per-shard backend still comes from the probe, fed the mesh's devices
    p = plan(q, num_shards=QX.mesh_num_shards(mesh),
             devices=list(mesh.devices.flat))
    assert p.backend == "reference"
    assert p.stages == ("partition", "local", "merge", "finalize")


def test_mesh_window_parity(rng, no_env_backend):
    mesh = _mesh8()
    g = jnp.array(rng.integers(0, 16, 4096).astype(np.int32))
    k = jnp.array(rng.integers(0, 1000, 4096).astype(np.int32))
    q = Query(ops=("sum", "count", "min", "max", "mean", "dc", "median"),
              window=Window(ws=1024, wa=256))
    ref, _ = execute(q, g, k, backend="reference", use_xla_sort=True)
    sh, _ = execute(q, g, k, mesh=mesh, use_xla_sort=True)
    _assert_result_equal(ref, sh)


def test_mesh_streaming_parity(rng, no_env_backend):
    mesh = _mesh8()
    g, k = _sorted_full(rng, 2048, 16)
    q = Query(ops=("sum", "count", "dc"), streaming=True)
    sa = sb = None
    for lo in range(0, 2048, 512):
        ra, sa = execute(q, g[lo:lo + 512], k[lo:lo + 512], state=sa,
                         backend="reference")
        rb, sb = execute(q, g[lo:lo + 512], k[lo:lo + 512], state=sb,
                         mesh=mesh)
        _assert_result_equal(ra, rb)


def test_mesh_jit_hot_loop(rng, no_env_backend):
    """The whole sharded pipeline is jit-compatible (the hot-loop form the
    serving step uses): one compiled call, shard_map inside."""
    mesh = _mesh8()
    g, k = _sorted_full(rng, 2048, 16)
    q = Query(ops=("sum", "dc"))
    p = plan(q, num_shards=QX.mesh_num_shards(mesh),
             devices=list(mesh.devices.flat))
    f = jax.jit(lambda a, b: execute(p, a, b, mesh=mesh)[0])
    sh = f(g, k)
    ref, _ = execute(q, g, k, backend="reference")
    _assert_result_equal(ref, sh)
