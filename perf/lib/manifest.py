"""BENCHMARK.json and the files it names.

Whatever belongs to one configuration, one traffic mix or one per-layer metric
sits in a file of its own, found here by the name the manifest gives it:
``perf/configs/<config>.json``, ``perf/traffic/<traffic>.json``,
``perf/metrics/<metric>.py``. Adding a cell adds files and entries only.
"""

import importlib.util
import json
import pathlib
import re

PERF = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERF.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def load(root=ROOT):
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r}; the manifest has "
        f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest, name):
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError(f"no configuration {name!r}")


def read_json(path):
    with open(path) as f:
        return json.load(f)


def config_file(manifest, name, root=ROOT):
    return read_json(pathlib.Path(root) / config_entry(manifest, name)["file"])


def traffic_file(name, perf=PERF):
    return read_json(pathlib.Path(perf) / "traffic" / f"{name}.json")


def load_module(path, name=None):
    """Import one file by path (names may hold '.' and '-')."""
    path = pathlib.Path(path)
    if not path.exists():
        raise ManifestError(f"{path} does not exist")
    mod_name = name or "perf_file_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name, perf=PERF):
    return load_module(pathlib.Path(perf) / "metrics" / f"{name}.py").read


def reference(config, perf=PERF):
    """The plain reference kept beside the configuration's file."""
    return load_module(
        pathlib.Path(perf) / "configs" / f"{config['reference']}.py")


def adapter(config, perf=PERF):
    """The only files that import the program: how a family is built and
    driven through its public entry points."""
    return load_module(
        pathlib.Path(perf) / "adapters" / f"{config['adapter']}.py")


def metrics_of(manifest, workload, group):
    """Metrics of ``group`` that the cell reports: those with no ``workloads``
    key, and those that list the cell."""
    out = []
    for m in manifest[group]:
        if "workloads" not in m or workload in m["workloads"]:
            out.append(m)
    return out


def validate(manifest, root=ROOT):
    """The contract's rules that a test can hold: names, units, files found
    by name, every per-layer metric moving an end-to-end metric its cells
    report. -> list of faults (empty = sound)."""
    bad = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"keys {sorted(set(manifest) ^ keys)}")
    root = pathlib.Path(root)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    names_c = [c["name"] for c in manifest["configs"]]
    names_w = [w["name"] for w in manifest["workloads"]]
    for group in (names, names_c, names_w):
        for n in group:
            if not NAME.match(n):
                bad.append(f"name {n!r}")
        if len(set(group)) != len(group):
            bad.append(f"duplicate in {group}")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source of {m['name']}")
        for w in m.get("workloads", []):
            if w not in names_w:
                bad.append(f"{m['name']} lists unknown cell {w}")
    for m in manifest["end_to_end"]:
        if set(m) - {"name", "unit", "better", "bound", "source", "workloads"}:
            bad.append(f"keys of {m['name']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"bound of {m['name']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end source of {m['name']}")
    for m in manifest["per_layer"]:
        if set(m) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            bad.append(f"keys of {m['name']}")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        moved = e2e[m["moves"]].get("workloads", names_w)
        for w in m.get("workloads", names_w):
            if w not in moved:
                bad.append(f"{m['name']} in {w}, which lacks {m['moves']}")
        if not (root / "perf" / "metrics" / f"{m['name']}.py").exists():
            bad.append(f"no reader for {m['name']}")
    used = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"keys of cell {w['name']}")
        if w["config"] not in names_c:
            bad.append(f"cell {w['name']} names unknown config")
        used.add(w["config"])
        if w["chips"] not in (1, 4):
            bad.append(f"chips of {w['name']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"why of {w['name']}")
        if not NAME.match(w["traffic"]):
            bad.append(f"traffic name {w['traffic']!r}")
        if not (root / "perf" / "traffic" / f"{w['traffic']}.json").exists():
            bad.append(f"no traffic file {w['traffic']}")
        reported = [m for m in metrics_of(manifest, w["name"], "end_to_end")]
        if len(reported) < 2:
            bad.append(f"cell {w['name']} reports only setup_s")
        if not metrics_of(manifest, w["name"], "per_layer"):
            bad.append(f"cell {w['name']} has no per-layer metric")
    for c in manifest["configs"]:
        if c["name"] not in used:
            bad.append(f"config {c['name']} is used by no cell")
        if not (root / c["file"]).exists():
            bad.append(f"no file {c['file']}")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in manifest["paths"]):
            bad.append(f"{c['file']} outside paths")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append("too many four-chip cells")
    return bad
