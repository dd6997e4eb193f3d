"""BENCHMARK.json: load it, hold it to the naming rules, look a cell up.

The rules are the driver's (names, units, what a cell must report); checking
them here means a later PR's new entry fails in a unit test, not on the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_END_TO_END = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    """BENCHMARK.json breaks a rule of the benchmark's contract."""


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything found by its names."""
    name: str
    config: str
    traffic: str
    chips: int
    config_file: str
    traffic_file: str
    cell_file: str
    end_to_end: tuple[dict[str, Any], ...]
    per_layer: tuple[dict[str, Any], ...]


def _line(text: Any, what: str) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ManifestError(f"{what} must be one line of 1 to 200 characters")


def _name(value: Any, what: str) -> None:
    if not (isinstance(value, str) and NAME.match(value)):
        raise ManifestError(f"{what} {value!r} is not a name (at most 64 of "
                            "letters, digits, '_', '.', '-')")


def _keys(entry: dict, required: set[str], optional: set[str], what: str) -> None:
    if not isinstance(entry, dict):
        raise ManifestError(f"{what} is not an object")
    missing, extra = required - set(entry), set(entry) - required - optional
    if missing or extra:
        raise ManifestError(f"{what}: missing keys {sorted(missing)}, "
                            f"unknown keys {sorted(extra)}")


def validate(doc: dict[str, Any]) -> None:
    """Raise :class:`ManifestError` where ``doc`` breaks a rule."""
    if set(doc) != TOP_KEYS:
        raise ManifestError(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
    if not (isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51):
        raise ManifestError("run_seconds must be a whole number from 1 to 51")
    for path in doc["paths"]:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path) or path.startswith("/") \
                or ".." in path.split("/"):
            raise ManifestError(f"path {path!r} is not a relative directory")
    if not 1 <= len(doc["paths"]) <= 16:
        raise ManifestError("1 to 16 paths")
    if not 1 <= len(doc["command"]) <= 32:
        raise ManifestError("command has 1 to 32 words")
    for word in doc["command"]:
        _line(word, "a word of command")

    configs: dict[str, dict] = {}
    for entry in doc["configs"]:
        _keys(entry, {"name", "source", "file", "reduced", "why"}, set(), "config")
        _name(entry["name"], "config name")
        _line(entry["source"], "config source")
        _line(entry["why"], "config why")
        if entry["name"] in configs:
            raise ManifestError(f"config {entry['name']!r} appears twice")
        if not any(entry["file"].startswith(p.rstrip("/") + "/") for p in doc["paths"]):
            raise ManifestError(f"config file {entry['file']!r} is outside paths")
        if len(entry["reduced"]) > 16:
            raise ManifestError("reduced has at most 16 keys")
        for key in entry["reduced"]:
            _name(key, "reduced key")
        configs[entry["name"]] = entry
    if len({c["file"] for c in configs.values()}) != len(configs):
        raise ManifestError("two configs share one file")

    cells: dict[str, dict] = {}
    pairs = set()
    for entry in doc["workloads"]:
        _keys(entry, {"name", "config", "traffic", "chips", "why"}, set(), "workload")
        for key in ("name", "config", "traffic"):
            _name(entry[key], f"workload {key}")
        _line(entry["why"], "workload why")
        if entry["chips"] not in (1, 4):
            raise ManifestError("chips is 1 or 4")
        if entry["config"] not in configs:
            raise ManifestError(f"workload {entry['name']!r} names no config")
        pair = (entry["config"], entry["traffic"])
        if entry["name"] in cells or pair in pairs:
            raise ManifestError(f"workload {entry['name']!r} appears twice")
        pairs.add(pair)
        cells[entry["name"]] = entry
    if not 1 <= len(cells) <= 24:
        raise ManifestError("1 to 24 workloads")
    unused = set(configs) - {c["config"] for c in cells.values()}
    if unused:
        raise ManifestError(f"configs used by no cell: {sorted(unused)}")
    four = sum(1 for c in cells.values() if c["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise ManifestError("more than a quarter of the cells ask for 4 chips")

    names: set[str] = set()
    end_to_end: dict[str, dict] = {}
    for entry in doc["end_to_end"]:
        _keys(entry, {"name", "unit", "better", "bound", "source"},
              {"workloads"}, "end_to_end metric")
        _metric(entry, names, cells)
        if entry["source"] not in SOURCES_END_TO_END:
            raise ManifestError(f"{entry['name']}: an end-to-end metric is read "
                                "from host_clock or device_trace")
        if not 0.01 <= entry["bound"] <= 0.1:
            raise ManifestError(f"{entry['name']}: bound outside 0.01..0.1")
        end_to_end[entry["name"]] = entry
    if "setup_s" not in end_to_end or "workloads" in end_to_end["setup_s"]:
        raise ManifestError("setup_s must be an end-to-end metric of every cell")
    for entry in doc["per_layer"]:
        _keys(entry, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, "per_layer metric")
        _metric(entry, names, cells)
        _line(entry["layer"], "layer")
        if entry["source"] not in SOURCES:
            raise ManifestError(f"{entry['name']}: unknown source")
        moved = end_to_end.get(entry["moves"])
        if moved is None:
            raise ManifestError(f"{entry['name']} moves no end-to-end metric")
        here = set(entry.get("workloads", cells))
        there = set(moved.get("workloads", cells))
        if not here <= there:
            raise ManifestError(
                f"{entry['name']} moves {entry['moves']}, which "
                f"{sorted(here - there)} do not report")
    for cell in cells:
        mine = [m for m in end_to_end.values() if cell in m.get("workloads", cells)]
        layers = [m for m in doc["per_layer"] if cell in m.get("workloads", cells)]
        if len(mine) < 2 or not layers:
            raise ManifestError(f"cell {cell!r} needs setup_s, another "
                                "end-to-end metric and a per-layer metric")


def _metric(entry: dict, names: set[str], cells: dict[str, dict]) -> None:
    _name(entry["name"], "metric name")
    if entry["name"] in names:
        raise ManifestError(f"metric {entry['name']!r} appears twice")
    names.add(entry["name"])
    if not (isinstance(entry["unit"], str) and UNIT.match(entry["unit"])):
        raise ManifestError(f"{entry['name']}: unit {entry['unit']!r}")
    if entry["better"] not in ("lower", "higher"):
        raise ManifestError(f"{entry['name']}: better is lower or higher")
    for cell in entry.get("workloads", ()):
        if cell not in cells:
            raise ManifestError(f"{entry['name']} lists unknown cell {cell!r}")


def load(path: str = MANIFEST) -> dict[str, Any]:
    with open(path, encoding="utf-8") as handle:
        doc = json.load(handle)
    validate(doc)
    return doc


def read_json(path: str) -> dict[str, Any]:
    with open(path, encoding="utf-8") as handle:
        return json.load(handle)


def load_by_name(directory: str, name: str, what: str,
                 needs: tuple[str, ...]) -> ModuleType:
    """``<directory>/<name>.py`` as a module: how a per-layer reader, a family
    and a reference are found from the name in a data file. Loaded once a
    path (a reference's jitted functions then trace once). No file there is a
    ``FileNotFoundError`` that says where it looked; a file that lacks one of
    ``needs`` is a ``ManifestError`` that says which."""
    if not (isinstance(name, str) and NAME.match(name)):
        raise ManifestError(f"{what} {name!r} is not a name")
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} {name!r}: looked at {path}")
    qualified = f"benchmark.{os.path.basename(directory)}.{name.replace('.', '_')}"
    module = sys.modules.get(qualified)
    if module is None or getattr(module, "__file__", None) != path:
        spec = importlib.util.spec_from_file_location(qualified, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[qualified] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[qualified]
            raise
    missing = [n for n in needs if not hasattr(module, n)]
    if missing:
        raise ManifestError(f"{what} {name!r} at {path} lacks {missing}")
    return module


def cell(doc: dict[str, Any], name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its files (found by name) and its metrics."""
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json; there are "
                            f"{[w['name'] for w in doc['workloads']]}")
    config = next(c for c in doc["configs"] if c["name"] == entry["config"])
    bench = os.path.join(root, os.path.basename(BENCH_DIR))

    def mine(metrics: list[dict]) -> tuple[dict, ...]:
        return tuple(m for m in metrics if name in m.get("workloads", [name]))

    return Cell(name=name, config=entry["config"], traffic=entry["traffic"],
                chips=entry["chips"],
                config_file=os.path.join(root, config["file"]),
                traffic_file=os.path.join(bench, "traffic", entry["traffic"] + ".json"),
                cell_file=os.path.join(bench, "cells", name + ".json"),
                end_to_end=mine(doc["end_to_end"]), per_layer=mine(doc["per_layer"]))
