"""BENCHMARK.json and the files it names.

The harness holds no cell's, configuration's, metric's or model family's
name in code: a cell names its configuration and traffic, and each is a
file found by that name; a configuration's file names its family, and
`benchmark/families/<family>.py` is the only file that knows the
architecture; a per-layer metric is a reader file found by the metric's
name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    """`benchmark/<kind>/<name>.json`, found by name."""
    if not NAME_RE.match(name):
        raise ManifestError(f"bad {kind} name {name!r}")
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything one run needs, resolved from the manifest by name."""
    man = load_manifest(root)
    cell = _named(man["workloads"], workload, "workload")
    cfg_entry = _named(man["configs"], cell["config"], "config")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"], root)

    def in_cell(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in man["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in man["per_layer"] if in_cell(m) and m["moves"] in reported
    ]
    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config_name": cell["config"],
        "traffic_name": cell["traffic"],
        "config": config,
        "family": load_family(config.get("family"), root),
        "traffic": traffic,
        "limits": load_json("limits", workload, root)["limits"],
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "run_seconds": man["run_seconds"],
    }


def load_reader(metric: str, root: str = ROOT):
    """The `read(run)` function of `benchmark/layer_metrics/<metric>.py`."""
    if not NAME_RE.match(metric):
        raise ManifestError(f"bad metric name {metric!r}")
    path = os.path.join(root, "benchmark", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", metric), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Family:
    """`benchmark/families/<name>.py`, found by the name a configuration's
    file gives and loaded when a loop or a reader first asks it for
    something (so after the chip is reached). What it has to expose is
    listed in PERF.md section 3; asked for a function it lacks, it raises
    `ManifestError` naming the family and the function."""

    def __init__(self, name: str, path: str):
        self.name, self.path = name, path
        self._module = None

    def __getattr__(self, attr: str):
        if attr.startswith("__"):  # copy, pickle and the like look these up
            raise AttributeError(attr)
        if self._module is None:
            spec = importlib.util.spec_from_file_location(
                "family_" + re.sub(r"[^A-Za-z0-9_]", "_", self.name), self.path
            )
            module = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = module  # dataclasses look their module up there
            spec.loader.exec_module(module)
            self._module = module
        try:
            return getattr(self._module, attr)
        except AttributeError:
            raise ManifestError(
                f"family {self.name!r} ({self.path}) has no {attr!r}"
            ) from None


def load_family(name: str, root: str = ROOT) -> Family:
    """`benchmark/families/<name>.py`, as `load_reader` finds a reader."""
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"bad family name {name!r}")
    path = os.path.join(root, "benchmark", "families", name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no family {name!r}: {path} is missing")
    return Family(name, path)


def _config_problems(entry: dict, root: str) -> list[str]:
    """What the family says of a configuration's file."""
    path = os.path.join(root, entry["file"])
    if not os.path.isfile(path):
        return [f"no file {entry['file']}"]
    with open(path) as f:
        cfg = json.load(f)
    try:
        return list(load_family(cfg.get("family"), root).check_config(cfg))
    except ManifestError as e:
        return [str(e)]


def validate(man: dict, root: str = ROOT) -> list[str]:
    """Problems with the manifest's names, units and files (empty = none)."""
    bad: list[str] = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: bad name {n!r}")

    cfgs = {c["name"] for c in man["configs"]}
    cells = {w["name"] for w in man["workloads"]}
    for c in man["configs"]:
        name_ok(c["name"], "config")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        bad += [f"config {c['name']}: {p}" for p in _config_problems(c, root)]
    for w in man["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not os.path.isfile(
            os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")
        ):
            bad.append(f"workload {w['name']}: no traffic file")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"workload {w['name']}: why must be one line <= 200")
    e2e = {m["name"] for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    seen: set[str] = set()
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            name_ok(m["name"], group)
            if m["name"] in seen:
                bad.append(f"{group}: duplicate metric {m['name']}")
            seen.add(m["name"])
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source={m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"{m['name']}: unknown workload {w}")
    for m in man["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    for m in man["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']}")
        if not os.path.isfile(
            os.path.join(root, "benchmark", "layer_metrics", m["name"] + ".py")
        ):
            bad.append(f"{m['name']}: no reader file")
    return bad
