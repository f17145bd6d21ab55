"""BENCHMARK.json and the files it names: loading, and the checks that can
be made without a device.  Everything that belongs to one configuration, one
traffic mix or one per-layer metric is a file found by its name here, so a
later PR adds a cell, a configuration or a metric with new files and new
entries and edits nothing that exists."""
from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_with_planned(root: str = ROOT) -> dict:
    """BENCHMARK.json plus the cells under benchmark/planned/: built and
    rehearsed, not yet admitted (PERF.md section 7).  Each file there is the
    fragment of entries a later benchmark PR adds, its bounds (null) still
    to be set from measurement; the knee sweep and the CPU rehearsal can
    already run them."""
    m = load(root)
    planned = os.path.join(HERE, "planned")
    for name in sorted(os.listdir(planned)):
        with open(os.path.join(planned, name)) as f:
            fragment = json.load(f)
        m["workloads"] = m["workloads"] + fragment["workloads"]
        m["end_to_end"] = fragment["end_to_end"] + m["end_to_end"]
        m["per_layer"] = m["per_layer"] + fragment["per_layer"]
    return m


def read_json(path: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, path)) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_of(manifest: dict, cell_: dict, root: str = ROOT) -> dict:
    entry = next(c for c in manifest["configs"] if c["name"] == cell_["config"])
    return read_json(entry["file"], root)


def traffic_path(traffic: str) -> str:
    return f"benchmark/traffic/{traffic}.json"


def metrics_of(manifest: dict, cell_name: str, group: str) -> list:
    """The metrics of `group` ("end_to_end" or "per_layer") that this cell
    reports: those without a `workloads` list, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def model_module(config: dict):
    return importlib.import_module(f"benchmark.models.{config['model']}")


def runner_module(traffic: dict):
    return importlib.import_module(f"benchmark.runners.{traffic['kind']}")


def reader_module(metric_name: str):
    return importlib.import_module(f"benchmark.metrics.{metric_name}")


def problems(manifest: dict, root: str = ROOT) -> list:
    """Everything wrong with the manifest that can be seen without running:
    an empty list is a pass.  The contract's own limits that the driver
    checks before any run, and this harness's: every file a name points to
    exists."""
    bad = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    if not (isinstance(manifest.get("run_seconds"), int)
            and 1 <= manifest["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        bad += [f"{group}: name {n!r} outside the allowed characters"
                for n in names if not NAME.match(n)]
        if len(set(names)) != len(names):
            bad.append(f"{group}: a name appears twice")
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in manifest[g]]
    if len(set(metric_names)) != len(metric_names):
        bad.append("a metric name is both end-to-end and per-layer")
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']}: used by no cell")
    cells = {w["name"] for w in manifest["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic name outside the allowed characters")
        if not os.path.isfile(os.path.join(root, traffic_path(w["traffic"]))):
            bad.append(f"cell {w['name']}: no file {traffic_path(w['traffic'])}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"cell {w['name']}: why is not 1 to 200 characters on one line")
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append(f"{four} cells ask for 4 chips")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"metric {m['name']}: unknown cell {w!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: an end-to-end metric is taken by the benchmark itself")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"metric {m['name']}: moves unknown metric {m['moves']!r}")
        if "bound" in m:
            bad.append(f"metric {m['name']}: a per-layer metric has no bound")
        if not os.path.isfile(os.path.join(root, "benchmark", "metrics", m["name"] + ".py")):
            bad.append(f"metric {m['name']}: no reader benchmark/metrics/{m['name']}.py")
    for w in manifest["workloads"]:
        mine = {m["name"] for m in metrics_of(manifest, w["name"], "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"cell {w['name']}: needs setup_s and one more end-to-end metric")
        layer = metrics_of(manifest, w["name"], "per_layer")
        if not layer:
            bad.append(f"cell {w['name']}: no per-layer metric")
        bad += [f"cell {w['name']}: {m['name']} moves {m['moves']}, which the cell does not report"
                for m in layer if m["moves"] not in mine]
    return bad
