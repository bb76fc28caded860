"""``BENCHMARK.json`` and the data files it names: loading, and the checks a cell must pass to run."""

from __future__ import annotations

import dataclasses
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def repo_root() -> str:
    """The checkout this package lies in (``benchmark/`` sits at its root)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files and metrics it resolves to."""

    name: str
    chips: int
    why: str
    config_name: str
    config: dict          # the configuration file, as run
    traffic_name: str
    mix: dict             # the traffic-mix file
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1
    root: str


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
    if not isinstance(data, dict):
        raise ManifestError(f"{path} does not hold a JSON object")
    return data


def load_manifest(root: "str | None" = None) -> dict:
    root = root or repo_root()
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in manifest:
            raise ManifestError(f"BENCHMARK.json lacks {key!r}")
    return manifest


def harness_dir(manifest: dict, root: str) -> str:
    """The directory under ``paths`` that holds the harness's data files
    (``traffic/``, ``layer_metrics/``, ``runners/``): the first of ``paths``."""
    return os.path.join(root, manifest["paths"][0])


def traffic_file(manifest: dict, root: str, name: str) -> str:
    base = os.path.join(harness_dir(manifest, root), "traffic", name)
    for suffix in DATA_SUFFIXES:
        if os.path.isfile(base + suffix):
            return base + suffix
    raise ManifestError(f"no traffic file for mix {name!r} at {base}.*")


def reader_file(manifest: dict, root: str, metric: str) -> str:
    return os.path.join(harness_dir(manifest, root), "layer_metrics",
                        metric + ".py")


def _reported_in(metric: dict, cell: str) -> bool:
    """A metric with no ``workloads`` key is reported by every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, root: "str | None" = None) -> Cell:
    """The cell called ``name``, with its configuration, mix and metrics."""
    root = root or repo_root()
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names unknown config "
                            f"{w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _read_json(traffic_file(manifest, root, w["traffic"]))
    e2e = tuple(m for m in manifest["end_to_end"] if _reported_in(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in manifest["per_layer"]
                      if _reported_in(m, name) and m["moves"] in reported)
    return Cell(name=name, chips=int(w["chips"]), why=w["why"],
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], mix=mix, end_to_end=e2e,
                per_layer=per_layer, root=root)


def validate(root: "str | None" = None) -> "list[str]":
    """Every breach of the contract's static rules that this repository can
    check for itself; an empty list means the manifest may be run."""
    root = root or repo_root()
    m = load_manifest(root)
    bad: "list[str]" = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: bad name {n!r}")

    def line_ok(s, what):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{what}: not one line of 1..200 characters")

    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32):
        bad.append("command: not a list of 1..32 strings")
    for word in m["command"]:
        line_ok(word, "command word")
    if not (isinstance(m["paths"], list) and 1 <= len(m["paths"]) <= 16):
        bad.append("paths: not 1..16 directories")
    for p in m["paths"]:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/")
                or ".." in p.split("/")):
            bad.append(f"paths: bad path {p!r}")
        elif not os.path.isdir(os.path.join(root, p)):
            bad.append(f"paths: {p!r} is not a directory")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad.append("run_seconds: not a whole number in 1..51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in m["paths"])

    seen: "set[str]" = set()
    files: "set[str]" = set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')!r}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        if c["name"] in seen:
            bad.append(f"config {c['name']!r} named twice")
        seen.add(c["name"])
        if not under_paths(c["file"]) or c["file"] in files:
            bad.append(f"config {c['name']!r}: file {c['file']!r} is outside "
                       "paths or used twice")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']!r}: no file {c['file']!r}")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']!r}: more than 16 reduced keys")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced key")
    config_names = set(seen)

    cells: "set[str]" = set()
    pairs: "set[tuple]" = set()
    used_configs: "set[str]" = set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')!r}: keys {sorted(w)}")
            continue
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], f"workload {w['name']} traffic")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["name"] in cells:
            bad.append(f"workload {w['name']!r} named twice")
        cells.add(w["name"])
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']!r}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["config"] not in config_names:
            bad.append(f"workload {w['name']!r}: unknown config")
        used_configs.add(w["config"])
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']!r}: chips must be 1 or 4")
        try:
            mix = _read_json(traffic_file(m, root, w["traffic"]))
            runner = os.path.join(harness_dir(m, root), "runners",
                                  str(mix.get("runner")) + ".py")
            if not os.path.isfile(runner):
                bad.append(f"workload {w['name']!r}: mix names runner "
                           f"{mix.get('runner')!r}, no {runner}")
        except ManifestError as e:
            bad.append(f"workload {w['name']!r}: {e}")
    for c in config_names - used_configs:
        bad.append(f"config {c!r} is used by no workload")
    if not 1 <= len(m["workloads"]) <= 24:
        bad.append("workloads: not 1..24 cells")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        bad.append(f"{four} four-chip cells is over a quarter of the cells")

    metrics: "set[str]" = set()
    e2e_cells: "dict[str, set]" = {}
    for e in m["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source"}
        if not allowed <= set(e) or set(e) - allowed - {"workloads"}:
            bad.append(f"end_to_end {e.get('name')!r}: keys {sorted(e)}")
            continue
        name_ok(e["name"], "end_to_end")
        if e["name"] in metrics:
            bad.append(f"metric {e['name']!r} named twice")
        metrics.add(e["name"])
        if not UNIT_RE.match(e["unit"]):
            bad.append(f"end_to_end {e['name']!r}: bad unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            bad.append(f"end_to_end {e['name']!r}: better={e['better']!r}")
        if e["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {e['name']!r}: source {e['source']!r}")
        if not (isinstance(e["bound"], (int, float)) and 0 < e["bound"] <= 0.1):
            bad.append(f"end_to_end {e['name']!r}: bound {e['bound']!r}")
        e2e_cells[e["name"]] = set(e.get("workloads", cells))
        for c in e.get("workloads", ()):
            if c not in cells:
                bad.append(f"end_to_end {e['name']!r}: unknown cell {c!r}")
    if "setup_s" not in metrics:
        bad.append("end_to_end lacks setup_s")
    elif e2e_cells["setup_s"] != cells:
        bad.append("setup_s is not reported by every cell")
    if not 1 <= len(m["end_to_end"]) <= 16:
        bad.append("end_to_end: not 1..16 metrics")

    layer_cells: "set[str]" = set()
    for e in m["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves"}
        if not allowed <= set(e) or set(e) - allowed - {"workloads"}:
            bad.append(f"per_layer {e.get('name')!r}: keys {sorted(e)}")
            continue
        name_ok(e["name"], "per_layer")
        if e["name"] in metrics:
            bad.append(f"metric {e['name']!r} named twice")
        metrics.add(e["name"])
        if not UNIT_RE.match(e["unit"]):
            bad.append(f"per_layer {e['name']!r}: bad unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            bad.append(f"per_layer {e['name']!r}: better={e['better']!r}")
        if e["source"] not in SOURCES:
            bad.append(f"per_layer {e['name']!r}: source {e['source']!r}")
        line_ok(e["layer"], f"per_layer {e['name']} layer")
        if e["moves"] not in e2e_cells:
            bad.append(f"per_layer {e['name']!r}: moves unknown metric "
                       f"{e['moves']!r}")
            continue
        for c in e.get("workloads", e2e_cells[e["moves"]]):
            if c not in cells:
                bad.append(f"per_layer {e['name']!r}: unknown cell {c!r}")
            elif c not in e2e_cells[e["moves"]]:
                bad.append(f"per_layer {e['name']!r}: cell {c!r} does not "
                           f"report {e['moves']!r}")
            layer_cells.add(c)
        if not os.path.isfile(reader_file(m, root, e["name"])):
            bad.append(f"per_layer {e['name']!r}: no reader "
                       f"{reader_file(m, root, e['name'])}")
    if not 1 <= len(m["per_layer"]) <= 128:
        bad.append("per_layer: not 1..128 metrics")
    for c in cells:
        others = [n for n, cs in e2e_cells.items()
                  if n != "setup_s" and c in cs]
        if not others:
            bad.append(f"cell {c!r} reports no end-to-end metric but setup_s")
        if c not in layer_cells:
            bad.append(f"cell {c!r} reports no per-layer metric")
    if os.path.getsize(os.path.join(root, "BENCHMARK.json")) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
