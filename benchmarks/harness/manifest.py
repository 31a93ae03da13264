"""Loader: from a cell's name in ``BENCHMARK.json`` to the files that
make it up.  Everything that belongs to one configuration, one traffic
mix or one per-layer metric sits in a file of its own, found by name:

    benchmarks/configs/<configuration>.json      (the manifest's "file")
    benchmarks/traffic/<traffic>.json
    benchmarks/layer_metrics/<metric>.json
    benchmarks/runners/<runner>.py               (the configuration's "runner")

so a later PR adds a cell by adding files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Any, Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH_DIR = "benchmarks"


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration's file, as run
    traffic: Dict[str, Any]         # the mix's parameters
    end_to_end: List[Dict[str, Any]]     # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]      # manifest entry + its reader file


def _read_json(path: pathlib.Path, what: str) -> Dict[str, Any]:
    if not path.is_file():
        raise ManifestError(f"{what}: no file {path}")
    try:
        data = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise ManifestError(f"{what}: {path} is not JSON: {e}") from e
    if not isinstance(data, dict):
        raise ManifestError(f"{what}: {path} does not hold an object")
    return data


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what}: {name!r} is not a name (letters, digits, '_', '.', "
            f"'-'; at most 64; no leading '.' or '-')")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"{what}: {unit!r} is not a unit (1 to 16 of letters, digits, "
            f"'_', '/', '%', '.', '-')")
    return unit


def load_manifest(root: pathlib.Path) -> Dict[str, Any]:
    man = _read_json(root / "BENCHMARK.json", "manifest")
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in man:
            raise ManifestError(f"manifest: no key {key!r}")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in man[group]:
            name = check_name(entry.get("name"), f"manifest {group}")
            if name in seen:
                raise ManifestError(f"manifest {group}: {name!r} twice")
            seen.add(name)
    for m in man["end_to_end"] + man["per_layer"]:
        check_unit(m.get("unit"), f"metric {m['name']}")
        if m.get("better") not in ("lower", "higher"):
            raise ManifestError(f"metric {m['name']}: better must be "
                                f"'lower' or 'higher'")
    return man


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise ManifestError(f"no workload {workload!r} in BENCHMARK.json "
                            f"(it has {sorted(cells)})")
    w = cells[workload]
    check_name(w.get("config"), f"workload {workload} config")
    check_name(w.get("traffic"), f"workload {workload} traffic")
    configs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {workload}: no configuration "
                            f"{w['config']!r} in BENCHMARK.json")
    config = _read_json(root / configs[w["config"]]["file"],
                        f"configuration {w['config']}")
    traffic = _read_json(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json",
                         f"traffic mix {w['traffic']}")
    e2e = [m for m in man["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    layer = []
    for m in man["per_layer"]:
        if not _applies(m, workload) or m.get("moves") not in e2e_names:
            continue
        spec = _read_json(root / BENCH_DIR / "layer_metrics"
                          / f"{m['name']}.json", f"per-layer metric {m['name']}")
        if "reader" not in spec:
            raise ManifestError(f"per-layer metric {m['name']}: its file "
                                f"names no reader")
        layer.append({**m, "reader": spec["reader"],
                      "args": spec.get("args", {})})
    return Cell(root=root, name=workload, chips=int(w.get("chips", 1)),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=layer)


def load_runner(root, name: str):
    """The module ``benchmarks/runners/<name>.py``; it has ``run(ctx)``."""
    check_name(name, "runner")
    path = pathlib.Path(root) / BENCH_DIR / "runners" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no runner {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.runners.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
