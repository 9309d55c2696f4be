"""``BENCHMARK.json``: load it, check its names, and find every file of a
cell by name.

A cell (an entry of ``workloads``) names a configuration, a traffic mix
and, through the metric lists, its per-layer metrics.  Each resolves to a
file of its own:

* configuration ``<c>``: the ``file`` its ``configs`` entry gives, whose
  ``entry`` key names the timed call, ``bench/entries/<entry>.py``, and
  whose ``reference`` key names ``bench/reference/<reference>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, whose ``loop`` key
  names the loop that drives it, ``bench/loops/<loop>.py``;
* metric ``<m>`` (end-to-end or per-layer): ``bench/metrics/<b>.py``,
  where ``<b>`` is ``<m>`` up to its first ``.`` (``tuples_per_s.x``
  and ``tuples_per_s.y`` share one reader), with ``read(ctx) -> float |
  None`` (``harness.Context``).

So a cell, a configuration, a traffic mix or a metric is added by adding
files and entries, never by editing the harness.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("device_trace", "host_clock")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries this cell reports untraced
    per_layer: tuple       # metric entries this cell reports traced
    root: Path = ROOT      # the checkout its files are found in

    def entry(self):
        return load_module(entry_path(self.config, self.root))

    def loop(self):
        return load_module(loop_path(self.traffic, self.root))

    def reference(self):
        return load_module(reference_path(self.config, self.root))

    def reader(self, metric: str):
        return load_module(metric_path(metric, self.root))


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def base_name(metric: str) -> str:
    """The quantity a metric reads: its name up to the first ``.``."""
    return metric.split(".", 1)[0]


def metric_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{base_name(name)}.py"


def entry_path(config: dict, root: Path = ROOT) -> Path:
    return root / "bench" / "entries" / f"{config['entry']}.py"


def loop_path(traffic: dict, root: Path = ROOT) -> Path:
    return root / "bench" / "loops" / f"{traffic['loop']}.py"


def reference_path(config: dict, root: Path = ROOT) -> Path:
    return root / "bench" / "reference" / f"{config['reference']}.py"


def load_module(path: Path):
    """Import one file as a module of its own (readers and references are
    found by path, so adding one needs no import line anywhere)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:           # per-layer: every cell of its metric
        return metric["moves"] in e2e_of_cell
    return True


def cell(manifest: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic loaded."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"]
                if c["name"] == entry["config"])
    e2e = tuple(m for m in manifest["end_to_end"]
                if _reports(m, name, set()))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in manifest["per_layer"]
                      if _reports(m, name, names))
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(traffic_path(entry["traffic"], root).read_text()),
        end_to_end=e2e, per_layer=per_layer, root=root)


def problems(manifest: dict, root: Path = ROOT) -> list[str]:
    """What in ``manifest`` breaks the benchmark's rules or fails to
    resolve; empty when it is sound."""
    out = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        out.append(f"top-level keys {sorted(manifest)} != {sorted(want)}")
        return out
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        out.append(f"run_seconds {rs!r} is not a whole number in 1..51")

    def name_ok(kind, value):
        if not (isinstance(value, str) and NAME.match(value)):
            out.append(f"{kind} {value!r} is not a valid name")

    def line_ok(kind, value):
        if not (isinstance(value, str) and 1 <= len(value) <= 200
                and "\n" not in value and "\t" not in value):
            out.append(f"{kind} {value!r} is not one line of 1..200 chars")

    for word in manifest["command"]:
        line_ok("command word", word)
    configs = {}
    for c in manifest["configs"]:
        name_ok("config", c["name"])
        line_ok("config source", c["source"])
        line_ok("config why", c["why"])
        for key in c["reduced"]:
            name_ok("reduced key", key)
        path = root / c["file"]
        if not path.is_file():
            out.append(f"config {c['name']}: no file {c['file']}")
            continue
        conf = json.loads(path.read_text())
        configs[c["name"]] = conf
        if conf.get("name") != c["name"]:
            out.append(f"{c['file']} names {conf.get('name')!r}, not "
                       f"{c['name']!r}")
        for kind, path_of in (("reference", reference_path),
                              ("entry", entry_path)):
            if not (isinstance(conf.get(kind), str)
                    and NAME.match(conf[kind])
                    and path_of(conf, root).is_file()):
                out.append(f"config {c['name']}: no {kind} module "
                           f"{conf.get(kind)!r}")
    cells = set()
    for w in manifest["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        line_ok("workload why", w["why"])
        cells.add(w["name"])
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config "
                       f"{w['config']!r}")
        tpath = traffic_path(w["traffic"], root)
        if not tpath.is_file():
            out.append(f"workload {w['name']}: no traffic file "
                       f"bench/traffic/{w['traffic']}.json")
        else:
            traffic = json.loads(tpath.read_text())
            if not (isinstance(traffic.get("loop"), str)
                    and NAME.match(traffic["loop"])
                    and loop_path(traffic, root).is_file()):
                out.append(f"workload {w['name']}: no loop module "
                           f"{traffic.get('loop')!r}")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips must be 1 or 4")
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in manifest[kind]]
        if len(set(names)) != len(names):
            out.append(f"duplicate names in {kind}")
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    if len(set(names)) != len(names):
        out.append("duplicate metric names")
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    for m in metrics:
        name_ok("metric", m["name"])
        if not UNIT.match(m["unit"]):
            out.append(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better must be lower|higher")
        for c in m.get("workloads", ()):
            if c not in cells:
                out.append(f"metric {m['name']}: unknown workload {c!r}")
        if not metric_path(m["name"], root).is_file():
            out.append(f"metric {m['name']}: no reader "
                       f"bench/metrics/{base_name(m['name'])}.py")
    for m in manifest["end_to_end"]:
        if m["source"] not in END_TO_END_SOURCES:
            out.append(f"end-to-end {m['name']}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.25:
            out.append(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        if m["source"] not in SOURCES:
            out.append(f"per-layer {m['name']}: source {m['source']!r}")
        line_ok(f"per-layer {m['name']} layer", m["layer"])
        if m["moves"] not in e2e_names:
            out.append(f"per-layer {m['name']}: moves unknown metric "
                       f"{m['moves']!r}")
    if out:
        return out
    for w in manifest["workloads"]:
        c = cell(manifest, w["name"], root)
        got = {m["name"] for m in c.end_to_end}
        if "setup_s" not in got or len(got) < 2:
            out.append(f"workload {w['name']} reports {sorted(got)}: it "
                       f"needs setup_s and one other end-to-end metric")
        if not c.per_layer:
            out.append(f"workload {w['name']} reports no per-layer metric")
        for m in c.per_layer:
            if m["moves"] not in got:
                out.append(f"per-layer {m['name']} moves {m['moves']}, "
                           f"which {w['name']} does not report")
    return out
