"""Run the aggregation engine's main path on one TPU chip and check it.

    python chip_smoke.py [--seed N]          # one chip: every phase below
    python chip_smoke.py --four-chips        # 4-device mesh: sharded phases

Each phase goes through the user entry points (``repro.query.execute`` or
``StreamingAggregator``) on data generated on the device from ``--seed``,
and is compared element for element with the ``reference`` backend on the
same data:

  * flat SWAG, ``Window(ws=1024, wa=256)``, three pushes of 2^22 tuples,
    for the paper engine's ops (min/max/sum/count), its dc variant, and
    median;
  * grouped aggregate: 2^24 group-sorted tuples over 2^16 groups;
  * per-group windows: ``ws_per_group=1024``, ``wa=128``, 256 groups,
    2^20 tuples (the per-pane partial path); and the same window with
    median and distinct count (the merge-replay path) on 2^16 tuples over
    16 groups — that path replays every store slot at every evaluation,
    so its memory grows with evaluations x capacity x window;
  * event-time windows, ``Window(range=2048, slide=512)`` over 2^20
    tuples with random timestamps: ungrouped min/max/sum/count (the
    two-stack), and grouped median and distinct count (per-window replay);
  * streaming windowed push: four pushes of 2^20 tuples through
    ``StreamingAggregator`` (reference backend by declared capability),
    checked against the batch per-group reference at each push boundary.

Per phase it prints the planned backend and the plan's note, whether the
lowered program holds a Pallas TPU kernel (``tpu_custom_call``), compile
and wall seconds, and the match.  It exits non-zero, without the final
line, when JAX finds no TPU, when a kernel phase is planned onto
``reference``, or when any result differs from the reference.  The last
line of a passing run is one JSON object naming the device.

``--four-chips`` runs only the sharded windowed (the flat SWAG window,
2^22 tuples) and grouped (2^24 tuples over 2^16 groups) queries over a
4-device mesh, each compared bit for bit with the same query on one device,
and prints the devices each shard's local results lived on.

The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set, else to
``.jax_cache/`` beside this script.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent
KERNEL_BACKENDS = ("pallas", "pallas-panes", "pallas-panestore")
PAPER_OPS = ("min", "max", "sum", "count")


class SmokeFailure(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Sizes:
    flat_n: int = 2 ** 22
    flat_pushes: int = 3
    flat_groups: int = 64
    ws: int = 1024
    wa: int = 256
    grouped_n: int = 2 ** 24
    grouped_groups: int = 2 ** 16
    pergroup_n: int = 2 ** 20
    pergroup_groups: int = 256
    pergroup_ws: int = 1024
    pergroup_wa: int = 128
    pergroup_merge_n: int = 2 ** 16
    pergroup_merge_groups: int = 16
    time_n: int = 2 ** 20
    time_span: int = 2 ** 21
    time_range: int = 2048
    time_slide: int = 512
    time_groups: int = 64
    stream_push: int = 2 ** 20
    stream_pushes: int = 4
    stream_groups: int = 64
    key_range: int = 1024


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.as_text()


def _data(seed: int, stream: int, n: int, groups: int, key_range: int,
          *, sort: bool = False):
    """``n`` (group, key) tuples made on the device from ``(seed, stream)``."""
    @jax.jit
    def make(key):
        kg, kk = jax.random.split(key)
        g = jax.random.randint(kg, (n,), 0, groups, jnp.int32)
        k = jax.random.randint(kk, (n,), 0, key_range, jnp.int32)
        return jax.lax.sort((g, k), num_keys=2) if sort else (g, k)

    return jax.block_until_ready(
        make(jax.random.fold_in(jax.random.key(seed), stream)))


@jax.jit
def _count_bad(got, want):
    """Mismatched elements between two AggResults of one shape: groups,
    validity and counts everywhere, values on the valid lanes."""
    bad = (jnp.sum(got.groups != want.groups)
           + jnp.sum(got.valid != want.valid)
           + jnp.sum(got.num_groups != want.num_groups))
    for name, w in want.values.items():
        bad += jnp.sum(want.valid & (got.values[name] != w))
    return bad


def _mismatches(got, want) -> int:
    """Compare on ``want``'s device; -1 when the layouts differ."""
    got, want = got._replace(stats=None), want._replace(stats=None)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(got),
                                               jax.tree.leaves(want))):
        return -1
    dev = jax.tree.leaves(want)[0].devices().pop()
    return int(_count_bad(jax.device_put(got, dev), want))


class Reference:
    """The ``reference`` backend on the same data, compiled by XLA for the
    host CPU by default: XLA's TPU compile time for the reference window
    and engine programs grows with the stream length, while the CPU
    compiles them in seconds.  ``device=None`` runs it on the chip."""

    def __init__(self, query, *, on_cpu: bool = True, **kwargs):
        from repro.query import execute
        self.device = jax.devices("cpu")[0] if on_cpu else None
        self._fn = jax.jit(lambda g, k: execute(
            query, g, k, backend="reference", **kwargs)[0]._replace(
                stats=None))

    def __call__(self, g, k):
        if self.device is not None:
            g, k = jax.device_put((g, k), self.device)
        return self._fn(g, k)

    @property
    def where(self) -> str:
        return "cpu" if self.device is not None else "chip"


def _kernel_phase(name: str, query, batches, ref: Reference,
                  **kwargs) -> None:
    """Plan ``query`` with ``auto`` (it must land on a kernel backend), run
    it through ``execute`` on every batch, and compare with ``ref``.
    ``kwargs`` go to ``execute`` (event-time timestamps: concrete host
    arrays, closed over, since the window layout is a static shape)."""
    from repro.query import execute, plan

    p = plan(query)
    _log(phase=name, backend=p.backend, note=p.note)
    if p.backend not in KERNEL_BACKENDS:
        raise SmokeFailure(f"{name}: planned onto {p.backend!r}, not a "
                           f"kernel backend")
    t0 = time.perf_counter()
    lowered = jax.jit(
        lambda g, k: execute(p, g, k, **kwargs)[0]._replace(
            stats=None)).lower(*batches[0])
    kernel = _has_kernel(lowered)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    if not kernel:
        raise SmokeFailure(f"{name}: lowered program has no tpu_custom_call")
    ok = True
    for i, (g, k) in enumerate(batches):
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(g, k))
        wall_s = time.perf_counter() - t0
        bad = _mismatches(got, ref(g, k))
        _log(phase=name, push=i, tuples=int(g.shape[0]),
             tpu_custom_call=kernel, compile_s=compile_s, wall_s=wall_s,
             reference_on=ref.where, mismatches=bad, match=bad == 0)
        ok &= bad == 0
    if not ok:
        raise SmokeFailure(f"{name}: results differ from the reference")


def _flat_phases(seed: int, sz: Sizes) -> None:
    from repro.configs import paper_engine
    from repro.query import Query, Window

    batches = [_data(seed, i, sz.flat_n, sz.flat_groups, sz.key_range)
               for i in range(sz.flat_pushes)]
    window = Window(ws=sz.ws, wa=sz.wa)
    for name, ops in (("flat-swag", paper_engine.config().op_list),
                      ("flat-swag-dc", paper_engine.config_dc().op_list),
                      ("flat-swag-median", ("median",))):
        q = Query(ops, window=window)
        _kernel_phase(name, q, batches, Reference(q, use_xla_sort=True))


def _grouped_phase(seed: int, sz: Sizes) -> None:
    from repro.query import Query

    q = Query(PAPER_OPS)
    _kernel_phase("grouped", q,
                  [_data(seed, 10, sz.grouped_n, sz.grouped_groups,
                         sz.key_range, sort=True)], Reference(q))


def _pergroup_phases(seed: int, sz: Sizes) -> None:
    """Per-group windows, with room in the store for every group's window:
    the paper engine's ops (the per-pane partial path), then median and
    distinct count (the merge-replay path).  The partial path's reference
    runs on the chip: on the CPU its per-slot ownership masks are not
    fused away and outgrow host memory.  The merge path's runs on the CPU:
    XLA's TPU layout pads its replay rows past the chip's memory."""
    from repro.kernels import registry
    from repro.query import Query, Window

    for name, ops, n, groups, stream, ref_on_cpu in (
            ("per-group", PAPER_OPS, sz.pergroup_n, sz.pergroup_groups, 20,
             False),
            ("per-group-merge", ("median", "dc"), sz.pergroup_merge_n,
             sz.pergroup_merge_groups, 21, True)):
        cap = groups * (sz.pergroup_ws // sz.pergroup_wa + 1)
        q = Query(ops, window=Window(ws=sz.pergroup_ws,
                                     ws_per_group=sz.pergroup_ws,
                                     wa=sz.pergroup_wa, capacity=cap))
        _log(phase=name, kernel_path=registry.pergroup_kernel_path(q))
        _kernel_phase(name, q, [_data(seed, stream, n, groups,
                                      sz.key_range)],
                      Reference(q, on_cpu=ref_on_cpu))


def _eventtime_phases(seed: int, sz: Sizes) -> None:
    """Time-range windows over random (unsorted) timestamps, made on the
    host: the window layout is computed there from concrete timestamps."""
    import numpy as np

    from repro.query import Query, Window, resolve_time_strategy

    ts = np.random.default_rng([seed, 40]).integers(
        0, sz.time_span, sz.time_n).astype(np.int32)
    window = Window(range=sz.time_range, slide=sz.time_slide)
    batch = [_data(seed, 41, sz.time_n, sz.time_groups, sz.key_range)]
    for name, q in (
            ("event-time", Query(PAPER_OPS, group_by=False, window=window)),
            ("event-time-grouped", Query(("median", "dc"), window=window))):
        _log(phase=name, strategy=resolve_time_strategy(q))
        _kernel_phase(name, q, batch, Reference(q, timestamps=ts),
                      timestamps=ts)


def _stream_phase(seed: int, sz: Sizes) -> None:
    """Streaming windowed pushes through ``StreamingAggregator``; the
    reference is the batch per-group query over the whole stream, read at
    each push boundary."""
    from repro.core.streaming import StreamingAggregator
    from repro.kernels import registry
    from repro.query import AggResult, Query, Window

    name = "stream-window"
    cap = sz.stream_groups * (sz.ws // sz.wa + 1)
    agg = StreamingAggregator("sum", window=Window(ws=sz.ws, wa=sz.wa,
                                                   capacity=cap))
    why = registry.get_backend("pallas").supports(agg.plan.query)
    _log(phase=name, backend=agg.plan.backend, note=agg.plan.note,
         declared=f"not a fallback: {why}")
    pushes = [_data(seed, 100 + i, sz.stream_push, sz.stream_groups,
                    sz.key_range) for i in range(sz.stream_pushes)]
    t0 = time.perf_counter()
    lowered = agg._step.lower(*pushes[0], agg.carry, None)
    kernel = _has_kernel(lowered)
    lowered.compile()
    compile_s = time.perf_counter() - t0

    ref = Reference(Query(("sum",), window=Window(
        ws=sz.ws, ws_per_group=sz.ws, wa=sz.wa, capacity=cap)))
    want = ref(jnp.concatenate([g for g, _ in pushes]),
               jnp.concatenate([k for _, k in pushes]))
    ok = True
    for i, (g, k) in enumerate(pushes):
        t0 = time.perf_counter()
        r = jax.block_until_ready(agg.push(g, k))
        wall_s = time.perf_counter() - t0
        row = (i + 1) * sz.stream_push // sz.wa - 1
        c = want.groups.shape[1]
        bad = _mismatches(
            AggResult(r.groups[:c], {"sum": r.values[:c]}, r.valid[:c],
                      r.num_groups),
            AggResult(want.groups[row], {"sum": want.values["sum"][row]},
                      want.valid[row], want.num_groups[row]))
        _log(phase=name, push=i, tuples=int(g.shape[0]),
             tpu_custom_call=kernel, compile_s=compile_s, wall_s=wall_s,
             reference_on=ref.where, mismatches=bad, match=bad == 0)
        ok &= bad == 0
    if not ok:
        raise SmokeFailure(f"{name}: results differ from the reference")


def run_one_chip(seed: int, sz: Sizes = Sizes()) -> None:
    _flat_phases(seed, sz)
    _grouped_phase(seed, sz)
    _pergroup_phases(seed, sz)
    _eventtime_phases(seed, sz)
    _stream_phase(seed, sz)


def _record_local_devices(log: list) -> None:
    """Note the devices each kernel call of a sharded local phase leaves
    its results on (the calls made inside the kernel-backend local phases
    of ``repro.distributed.query_exec``; the merge stage's are not local)."""
    from repro.distributed import query_exec as qx
    from repro.kernels.groupagg import ops as gops
    from repro.kernels.swag import ops as sops

    local = [False]

    def in_local(mod, attr):
        fn = getattr(mod, attr)

        def wrapped(*args, **kwargs):
            local[0] = True
            try:
                return fn(*args, **kwargs)
            finally:
                local[0] = False

        setattr(mod, attr, wrapped)

    def recorded(mod, attr):
        fn = getattr(mod, attr)

        def wrapped(*args, **kwargs):
            out = fn(*args, **kwargs)
            if local[0]:
                leaf = jax.tree.leaves(out)[0]
                log.append(sorted(str(d) for d in leaf.devices()))
            return out

        setattr(mod, attr, wrapped)

    in_local(qx, "_local_engine_tables")
    in_local(qx, "_window_partitioned")
    recorded(gops, "_groupagg_kernel_exec")
    recorded(sops, "_swag_kernel_exec")


def run_four_chips(seed: int, sz: Sizes = Sizes()) -> None:
    from repro.query import Query, Window, execute, plan

    devices = jax.devices()
    if len(devices) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    mesh = jax.make_mesh((4,), ("shards",), devices=devices[:4])
    shard_log: list = []
    _record_local_devices(shard_log)
    cases = [
        ("sharded-window", Query(PAPER_OPS, window=Window(ws=sz.ws,
                                                          wa=sz.wa)),
         _data(seed, 31, sz.flat_n, sz.flat_groups, sz.key_range)),
        ("sharded-grouped", Query(PAPER_OPS),
         _data(seed, 30, sz.grouped_n, sz.grouped_groups, sz.key_range,
               sort=True)),
    ]
    ok = True
    for name, q, (g, k) in cases:
        p = plan(q, num_shards=4, devices=devices[:4])
        _log(phase=name, backend=p.backend, note=p.note, shards=4)
        if p.backend not in KERNEL_BACKENDS:
            raise SmokeFailure(f"{name}: planned onto {p.backend!r}")
        walls = []
        for _ in range(2):      # the first call compiles, eagerly
            del shard_log[:]
            t0 = time.perf_counter()
            got = jax.block_until_ready(execute(q, g, k, mesh=mesh)[0])
            walls.append(time.perf_counter() - t0)
        local_devices = list(shard_log)
        t0 = time.perf_counter()
        want = jax.block_until_ready(jax.jit(
            lambda g, k: execute(q, g, k)[0]._replace(stats=None))(g, k))
        single_s = time.perf_counter() - t0
        bad = _mismatches(got, want)
        _log(phase=name, tuples=int(g.shape[0]),
             local_kernel_call_devices=local_devices,
             result_devices=sorted(str(d) for d in got.groups.devices()),
             sharded_first_call_s=walls[0], sharded_wall_s=walls[1],
             single_device_jit_first_call_s=single_s,
             mismatches=bad, bit_identical=bad == 0)
        ok &= bad == 0
    if not ok:
        raise SmokeFailure("sharded results differ from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phases on a 4-device mesh")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    try:
        if args.four_chips:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
