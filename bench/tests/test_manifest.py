"""``BENCHMARK.json`` keeps the benchmark's rules, and every cell,
configuration, traffic mix, reference and metric resolves by name."""
from __future__ import annotations

import copy
import json
import shutil

import pytest

import manifest


def test_manifest_is_sound():
    assert manifest.problems(manifest.load()) == []


def test_every_cell_resolves():
    m = manifest.load()
    for w in m["workloads"]:
        cell = manifest.cell(m, w["name"])
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["name"] == w["config"]
        for metric in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(metric["name"]).read)
        ref = cell.reference()
        assert callable(ref.evaluate) and callable(ref.control)
        assert callable(ref.bytes_per_push)
        assert callable(cell.entry().build)
        assert callable(cell.loop().measure)


def test_split_metrics_share_a_reader():
    assert manifest.base_name("tuples_per_s.per_group") == "tuples_per_s"
    assert manifest.metric_path("kernel_ms_per_push.per_group") == \
        manifest.metric_path("kernel_ms_per_push")


@pytest.mark.parametrize("where, bad", [
    ("workloads", {"name": "bad name"}),
    ("workloads", {"traffic": "no/slash"}),
    ("end_to_end", {"unit": "tuples per second"}),
    ("end_to_end", {"bound": 0.3}),
    ("per_layer", {"name": "µs_metric"}),
    ("per_layer", {"moves": "no_such_metric"}),
    ("per_layer", {"source": "guess"}),
])
def test_bad_entries_are_flagged(where, bad):
    m = copy.deepcopy(manifest.load())
    m[where][0].update(bad)
    assert manifest.problems(m)


def test_a_cell_is_added_by_files_and_entries_only(tmp_path,
                                                   kernels_on_cpu):
    """A new configuration (with its own entry and reference), traffic mix
    (with its own loop) and metrics, each a file of its own plus an entry
    in ``BENCHMARK.json``, resolve and run without any change to the
    harness."""
    import time

    import harness

    bench = tmp_path / "bench"
    shutil.copytree(manifest.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(bench / "entries/execute.py", bench / "entries/new_entry.py")
    shutil.copy(bench / "loops/closed.py", bench / "loops/new_loop.py")
    shutil.copy(bench / "reference/count_window.py",
                bench / "reference/new_reference.py")
    (bench / "metrics/pushes.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.latencies))\n")
    (bench / "metrics/new_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    conf = {
        "name": "new-config", "entry": "new_entry",
        "reference": "new_reference", "kernel_backends": ["pallas-panes"],
        "query": {"ops": ["min", "sum", "count"],
                  "window": {"ws": 256, "wa": 64}},
        "data": {"columns": [
            {"name": "groups", "dist": "zipf", "high": 8, "s": 1.1},
            {"name": "keys", "dist": "uniform", "low": 0, "high": 64}]}}
    (bench / "configs/new-config.json").write_text(json.dumps(conf))
    (bench / "traffic/new-mix.json").write_text(json.dumps({
        "name": "new-mix", "loop": "new_loop", "in_flight": 1,
        "push_tuples": 2048, "pool": 2, "check_sample": 2,
        "warmup_pushes": 1, "trace_seconds": 1}))
    m = manifest.load()
    m["configs"].append({"name": "new-config", "source": "a test",
                         "file": "bench/configs/new-config.json",
                         "reduced": [], "why": "a configuration by files"})
    m["workloads"].append({"name": "new.cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1,
                           "why": "a cell added by files"})
    m["end_to_end"].append({"name": "pushes", "unit": "pushes",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["new.cell"]})
    m["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "pushes",
                           "workloads": ["new.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(m, tmp_path) == []
    cell = manifest.cell(m, "new.cell", tmp_path)
    assert cell.config["name"] == "new-config"
    assert cell.traffic["name"] == "new-mix"
    assert [x["name"] for x in cell.per_layer] == ["new_metric"]
    assert {x["name"] for x in cell.end_to_end} == {"setup_s", "pushes"}
    line = harness.run(cell, 2 ** 40 + 3, 0.3, False,
                       t_process=time.perf_counter(), require_chip=False)
    assert line["correct"] is True
    assert line["metrics"]["pushes"]["value"] == line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "pushes"}
