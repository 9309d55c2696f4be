"""Device time by the program's stages (``stages.py`` and the stage
readers), on small traces recorded on a TPU v5e with the stage scopes
(``record_stages.py``), on the traces recorded before the scopes existed,
and on the CPU."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import harness
import manifest
import stages
import trace_reduce as tr
from tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
#: each cell's stages that do device work, and those holding its kernels
CELLS = {
    "enthuse.flat-swag": ({"sort_panes", "pane_merge", "assemble"},
                          {"sort_panes", "pane_merge"}),
    "enthuse.per-group": ({"frame", "dir_scan", "dir_snapshot",
                           "slot_partials", "slot_fold", "assemble"},
                          {"slot_partials"}),
}
#: the stage readers of each cell, by reader
READERS = {"enthuse.flat-swag": {"sort_ms_per_push", "pane_merge_ms_per_push",
                                 "unstaged_ms_per_push"},
           "enthuse.per-group": {"dir_scan_ms_per_push",
                                 "slot_fold_ms_per_push",
                                 "unstaged_ms_per_push"}}


def _recorded(name: str, folder: Path = DATA / "stages"):
    meta = json.loads((folder / f"{name}.json").read_text())
    return meta, tr.load(str(folder / f"{name}.xplane.pb"), 1,
                         meta["kernels"])


def _ctx(cell, trace):
    peaks = json.loads((manifest.BENCH / "peaks.json").read_text())
    return harness.Context(
        config=cell.config, traffic=cell.traffic,
        reference=cell.reference(),
        peaks=peaks["devices"]["TPU v5 lite"], setup_s=1.0,
        window=harness.Window(latencies=[0.1], seconds=0.1, sample=[]),
        trace=trace)


def _stage_readers(cell) -> dict:
    return {manifest.base_name(m["name"]): cell.reader(m["name"])
            for m in cell.per_layer
            if manifest.base_name(m["name"]) in READERS[cell.name]}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_stages_cover_the_recorded_trace(name):
    """Stage sums plus ``unstaged`` are the trace's whole self time; every
    Pallas kernel lies in a stage of its own (beside XLA ops of the same
    stage)."""
    meta, r = _recorded(name)
    names = meta["stages"]
    working, holding = CELLS[name]
    per_stage = {s: stages.self_s(r, names, s) for s in set(names.values())}
    assert {s for s, t in per_stage.items() if t > 0} == working
    total = r.self_s(kernel=True) + r.self_s(kernel=False)
    unstaged = stages.self_s(r, names, None)
    assert sum(per_stage.values()) + unstaged == pytest.approx(total,
                                                               rel=1e-9)
    kernels = {names.get(o.name.rsplit("/", 1)[-1])
               for o in r.ops[0] if o.kernel}
    assert kernels == holding
    assert sum(per_stage[s] for s in holding) >= r.self_s(kernel=True)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_stage_readers_on_recorded_trace(name, monkeypatch):
    """The stage readers split what the outside-in readers read, and read
    it unchanged."""
    meta, r = _recorded(name)
    monkeypatch.setattr(stages, "of_cell", lambda config, traffic:
                        meta["stages"])
    cell = tiny_cell(name)
    ctx = _ctx(cell, r)
    got = {b: reader.read(ctx) for b, reader in _stage_readers(cell).items()}
    assert set(got) == READERS[name] and all(v >= 0 for v in got.values())
    outside = (cell.reader("xla_ms_per_push").read(ctx)
               + cell.reader("kernel_ms_per_push").read(ctx))
    assert outside == pytest.approx((r.self_s(kernel=True)
                                     + r.self_s(kernel=False))
                                    / r.pushes * 1e3, rel=1e-12)
    staged = sum(stages.self_s(r, meta["stages"], s)
                 for s in set(meta["stages"].values())) / r.pushes * 1e3
    assert staged + got["unstaged_ms_per_push"] == pytest.approx(
        outside, rel=1e-9)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_without_stages_reads_nothing(name, monkeypatch):
    """A program from before the scopes (the traces recorded then) names
    no stage: every stage reader finds nothing, and none raises."""
    meta = json.loads((DATA / f"{name}.json").read_text())
    r = tr.load(str(DATA / f"{name}.xplane.pb"), 1, meta["kernels"])
    monkeypatch.setattr(stages, "of_cell", lambda config, traffic: {})
    cell = tiny_cell(name)
    ctx = _ctx(cell, r)
    assert {b: reader.read(ctx) for b, reader
            in _stage_readers(cell).items()} == dict.fromkeys(READERS[name])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_stage_map_is_the_entry_program(name, kernels_on_cpu):
    """The readers' map comes from the cell's timed call compiled again:
    the same instructions as the entry's own compile."""
    import gen
    cell = tiny_cell(name)
    batches = gen.batches(cell.config, cell.traffic, 3)
    system = cell.entry().build(cell, batches, False)
    want = stages.stage_names(system.call.as_text())
    assert set(want.values()) >= CELLS[name][1] | {"assemble"}
    assert stages.of_cell(cell.config, cell.traffic) == want
