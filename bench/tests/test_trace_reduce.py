"""The reduction from a profiler trace to per-layer numbers, on small
traces recorded on a TPU v5e (``record_trace.py``) and on hand-made
events."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import trace_reduce as tr
from tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
CELLS = {"enthuse.flat-swag": {"_pane_kernel", "_sort_panes_kernel"},
         "enthuse.per-group": {"_pergroup_fused_kernel"}}
K = 'custom_call_target="tpu_custom_call"'


def test_nesting_gives_self_time():
    texts = [(0, 100, "%while.1 = (s32[]) while(...)"),
             (10, 30, "%fusion.2 = s32[] fusion(...)"),
             (40, 90, f"%k.3 = s32[8] custom-call(...), {K}"),
             (50, 60, "%inner.4 = s32[] add(...)"),
             (120, 150, "ROOT %copy.5 = s32[8] copy(...)")]
    ops = tr._nest([(s, e, tr.instruction(t), tr.KERNEL_MARK in t)
                    for s, e, t in texts])
    by = {o.name: o for o in ops}
    assert by["while.1"].self_ns == 100 - 20 - 50
    assert by["while.1/k.3"].self_ns == 40 and by["while.1/k.3"].kernel
    assert by["while.1/inner.4"].self_ns == 10
    assert by["copy.5"].top and not by["while.1/fusion.2"].top
    assert sum(o.self_ns for o in ops) == 100 + 30


def test_kernel_names_match_by_shape():
    lowered = ('%4:2 = stablehlo.custom_call @tpu_custom_call(%2) {bc, '
               'kernel_name = "_a_kernel"} : (tensor<8x1x256xi32>) -> '
               '(tensor<8x1x256xi32>, tensor<8x1x256xi32>)\n'
               '%5 = stablehlo.custom_call @tpu_custom_call(%4#0) {bc, '
               'kernel_name = "_b_kernel"} : (tensor<8x1x256xi32>) -> '
               'tensor<5x1024xi32>\n')
    compiled = (f'  %f.2 = (s32[8,1,256]{{2,1,0}}, s32[8,1,256]{{2,1,0}}) '
                f'custom-call(%x), {K}\n'
                f'  ROOT %f.3 = s32[5,1024]{{1,0}} custom-call(%y), {K}\n')
    assert tr.kernel_names(lowered, compiled) == {"f.2": "_a_kernel",
                                                  "f.3": "_b_kernel"}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_recorded_trace(name):
    meta = json.loads((DATA / f"{name}.json").read_text())
    r = tr.load(str(DATA / f"{name}.xplane.pb"), 1, meta["kernels"])
    assert r.pushes == meta["pushes"] >= 1
    assert set(meta["kernels"].values()) == CELLS[name]
    busy, window = r.busy_s(), r.window_s()
    assert 0 < busy <= window
    kernel, xla = r.self_s(kernel=True), r.self_s(kernel=False)
    assert kernel > 0 and xla > 0
    # self times of ops in one stream add up to the busy time, but for
    # the nanosecond rounding of each op's picosecond times
    n_ops = len(r.ops[0])
    assert kernel + xla == pytest.approx(busy, abs=2e-9 * n_ops)
    assert len(r.push_ns) == r.pushes and min(r.push_ns) > 0
    b = r.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    kernel_labels = {r._label(o) for o in r.ops[0] if o.kernel}
    assert kernel_labels and all(any(k in lab for k in CELLS[name])
                                 for lab in kernel_labels)
    assert all(s >= 0 for _, s in b["device_ops"] + b["idle_gaps"])
    assert {n for n, _ in b["idle_gaps"]} <= {tr.PUSH_SPAN, tr.WAIT_SPAN,
                                              "harness"}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_readers_on_recorded_trace(name):
    import harness
    import manifest
    meta = json.loads((DATA / f"{name}.json").read_text())
    cell = tiny_cell(name)
    peaks = json.loads((manifest.BENCH / "peaks.json").read_text())
    ctx = harness.Context(
        config=cell.config, traffic=cell.traffic,
        reference=cell.reference(),
        peaks=peaks["devices"]["TPU v5 lite"], setup_s=1.0,
        window=harness.Window(latencies=[0.1], seconds=0.1, sample=[]),
        trace=tr.load(str(DATA / f"{name}.xplane.pb"), 1, meta["kernels"]))
    values = {manifest.base_name(m["name"]): cell.reader(m["name"]).read(ctx)
              for m in cell.per_layer}
    assert len(values) == 5 and all(v is not None for v in values.values())
    assert 0 <= values["device_idle_share"] < 100
    assert 0 < values["hbm_roofline_share"] <= 100
    assert values["kernel_ms_per_push"] > 0 and values["xla_ms_per_push"] > 0
    assert values["dispatch_ms_per_push"] > 0


def test_dropped_events_cut_the_window():
    """Pushes whose results came after the profiler dropped events are
    not read."""
    meta = json.loads((DATA / "enthuse.flat-swag.json").read_text())
    path = str(DATA / "enthuse.flat-swag.xplane.pb")
    whole = tr.load(path, 1, meta["kernels"])
    if whole.pushes < 2:
        pytest.skip("the recorded trace holds one push")
    from unittest import mock
    cut = whole.window[0] + (whole.window[1] - whole.window[0]) // 2
    real = tr._dropped_marker
    with mock.patch.object(tr, "_dropped_marker",
                           lambda pd: min(cut, real(pd) or cut)):
        part = tr.load(path, 1, meta["kernels"])
    assert 1 <= part.pushes < whole.pushes
    assert part.window[1] <= cut
